"""Unit tests for the utils layer (config, cmdline, hashing, rng, buffers)."""

import os

import numpy as np
import pytest

from swiftmpi_tpu.utils import (BinaryBuffer, CMDLine, ConfigError,
                                ConfigParser, Error, Random, TextBuffer,
                                Timer, bkdr_hash, bkdr_hash_batch,
                                get_hash_code, get_hash_code_np,
                                global_config, global_random)


# -- config ---------------------------------------------------------------

def test_config_parses_reference_demo_conf_format(tmp_path):
    # Format per reference apps/word2vec/demo.conf
    conf = tmp_path / "demo.conf"
    conf.write_text(
        "# comment\n"
        "[cluster]\n"
        "server_num: 2\n"
        "to_split_worker_server: 0\n"
        "\n"
        "[server]\n"
        "frag_num: 2000\n"
        "shard_num: 20\n"
        "initial_learning_rate: 0.05\n"
        "[word2vec]\n"
        "len_vec: 100  # trailing comment\n"
        "window 4\n"  # space-separated form
    )
    cfg = ConfigParser(str(conf))
    assert cfg.get("cluster", "server_num").to_int32() == 2
    assert cfg.get("server", "initial_learning_rate").to_float() == pytest.approx(0.05)
    assert cfg.get("word2vec", "len_vec").to_int32() == 100
    assert cfg.get("word2vec", "window").to_int32() == 4
    assert not cfg.get("cluster", "to_split_worker_server").to_bool()


def test_config_import_directive(tmp_path):
    base = tmp_path / "base.conf"
    base.write_text("[server]\nshard_num: 8\n")
    main = tmp_path / "main.conf"
    main.write_text("import base.conf\n[server]\nfrag_num: 100\n")
    cfg = ConfigParser(str(main))
    assert cfg.get("server", "shard_num").to_int32() == 8
    assert cfg.get("server", "frag_num").to_int32() == 100


def test_config_import_section_persists_after_import(tmp_path):
    # Reference parser keeps cur_session as member state: a [section]
    # opened inside an imported file stays current in the importer.
    base = tmp_path / "base.conf"
    base.write_text("[server]\nshard_num: 8\n")
    main = tmp_path / "main.conf"
    main.write_text("import base.conf\nfrag_num: 100\n")
    cfg = ConfigParser(str(main))
    assert cfg.get("server", "frag_num").to_int32() == 100


def test_config_key_starting_with_import_is_not_a_directive(tmp_path):
    conf = tmp_path / "x.conf"
    conf.write_text("[s]\nimportant_flag: 1\n")
    cfg = ConfigParser(str(conf))
    assert cfg.get("s", "important_flag").to_int32() == 1


def test_config_missing_key_raises():
    cfg = ConfigParser()
    with pytest.raises(ConfigError):
        cfg.get("nope", "missing")


def test_global_config_update_from_code():
    global_config().update({"server": {"shard_num": 4}})
    assert global_config().get("server", "shard_num").to_int32() == 4


# -- cmdline --------------------------------------------------------------

def test_cmdline_reference_style_flags():
    cmd = CMDLine(["prog", "-config", "demo.conf", "-niters", "10",
                   "-data", "x.txt", "-help"])
    assert cmd.getValue("config") == "demo.conf"
    assert cmd.getValue("niters") == "10"
    assert cmd.hasParameter("help")
    assert not cmd.hasParameter("output")
    assert cmd.getValue("output", "fallback.txt") == "fallback.txt"
    with pytest.raises(KeyError):
        cmd.getValue("output")


# -- hashing --------------------------------------------------------------

def test_murmur_finalizer_known_values():
    # Golden values computed from the murmur3 fmix64 spec (the reference's
    # get_hash_code is exactly fmix64, HashFunction.h:16-24).
    assert get_hash_code(0) == 0
    assert get_hash_code(1) == 0xB456BCFC34C2CB2C
    assert get_hash_code(0xDEADBEEF) == 0xD24BD59F862A1DAC


def test_murmur_vectorized_matches_scalar():
    keys = np.array([0, 1, 2, 12345, 0xDEADBEEF, 2**63 + 17], dtype=np.uint64)
    vec = get_hash_code_np(keys)
    for k, v in zip(keys.tolist(), vec.tolist()):
        assert get_hash_code(int(k)) == int(v)


def test_bkdr_hash_spec():
    # hash = hash*13131 + ch over uint32 (reference string.h:130-137)
    assert bkdr_hash("a") == ord("a")
    assert bkdr_hash("ab") == (ord("a") * 13131 + ord("b")) % 2**32
    batch = bkdr_hash_batch(["a", "ab", "hello"])
    assert batch[0] == ord("a")
    assert batch[1] == bkdr_hash("ab")
    assert batch[2] == bkdr_hash("hello")


# -- rng ------------------------------------------------------------------

def test_lcg_recurrence_matches_spec():
    r = Random(seed=1)
    # next = seed*25214903917 + 11 mod 2^64 (reference random.h:28-31)
    assert r() == (1 * 25214903917 + 11) % 2**64
    v2 = ((1 * 25214903917 + 11) * 25214903917 + 11) % 2**64
    assert r() == v2


def test_lcg_batch_matches_sequential():
    r1, r2 = Random(seed=42), Random(seed=42)
    seq = [r1() for _ in range(16)]
    assert r2.batch(16).tolist() == seq
    assert r1() == r2()  # state advanced identically


def test_gen_float_in_unit_interval_and_deterministic():
    r1, r2 = Random(2008), Random(2008)
    vals = [r1.gen_float() for _ in range(100)]
    assert all(0.0 <= v <= 1.0 for v in vals)
    assert vals == [r2.gen_float() for _ in range(100)]
    assert global_random()() == Random(2008)()


# -- buffers --------------------------------------------------------------

def test_binary_buffer_roundtrip_scalars():
    bb = BinaryBuffer()
    bb.put_int32(-7).put_uint64(2**40).put_float(1.5).put_bool(True)
    assert bb.get_int32() == -7
    assert bb.get_uint64() == 2**40
    assert bb.get_float() == pytest.approx(1.5)
    assert bb.get_bool() is True
    assert bb.read_finished


def test_binary_buffer_little_endian_wire_format():
    # Raw memcpy little-endian, matching the reference BinaryBuffer wire
    # format (Buffer.h:169-230): int32 1 must be 01 00 00 00.
    bb = BinaryBuffer()
    bb.put_int32(1)
    assert bb.to_bytes() == b"\x01\x00\x00\x00"


def test_binary_buffer_array_roundtrip():
    arr = np.arange(6, dtype=np.float32)
    bb = BinaryBuffer()
    bb.put_array(arr)
    out = bb.get_array(6, np.float32)
    np.testing.assert_array_equal(arr, out)


def test_binary_buffer_array_underflow_raises():
    bb = BinaryBuffer()
    bb.put_array(np.arange(3, dtype=np.float32))
    with pytest.raises(ValueError):
        bb.get_array(10, np.float32)


def test_cmdline_negative_numeric_values():
    cmd = CMDLine(["p", "-lr", "-0.5", "-sample", "-1", "-flag"])
    assert cmd.getValue("lr") == "-0.5"
    assert cmd.getValue("sample") == "-1"
    assert cmd.hasParameter("flag")


def test_text_buffer():
    tb = TextBuffer()
    tb.put(1, " ", 2.5, " ", "x")
    assert tb.tokens() == ["1", "2.5", "x"]


# -- timers ---------------------------------------------------------------

def test_timer_and_error():
    t = Timer(time_limit_s=1000)
    assert t.elapsed() >= 0
    assert not t.timeout()
    e = Error()
    e.accu(2.0)
    e.accu(4.0)
    assert e.norm() == pytest.approx(3.0)
    e.reset()
    assert e.norm() == 0.0


def test_xla_env_import_is_jax_free():
    """utils/xla_env must be importable BEFORE jax initializes (its whole
    purpose is setting XLA_FLAGS pre-init) — so the package __init__
    chains it pulls in must never import jax at module level.  Pins the
    contract tests/conftest.py and __graft_entry__.py rely on."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; "
         "from swiftmpi_tpu.utils.xla_env import ensure_cpu_mesh_flags; "
         "import os; os.environ.pop('XLA_FLAGS', None); "
         "ensure_cpu_mesh_flags(n_devices=3, force_device_count=True); "
         "assert '=3' in os.environ['XLA_FLAGS']; "
         "assert 'jax' not in sys.modules, 'xla_env import pulled in jax'"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_cpu_mesh_flags_append_both_collective_timeouts(monkeypatch):
    """The installed jaxlib knows both rendezvous-timeout flags, so they
    are appended unprobed; a caller's own value for either one wins."""
    from swiftmpi_tpu.utils.xla_env import ensure_cpu_mesh_flags

    warn = "--xla_cpu_collective_call_warn_stuck_timeout_seconds"
    term = "--xla_cpu_collective_call_terminate_timeout_seconds"
    monkeypatch.setenv("XLA_FLAGS", "")
    ensure_cpu_mesh_flags()
    assert f"{warn}=60" in os.environ["XLA_FLAGS"]
    assert f"{term}=600" in os.environ["XLA_FLAGS"]
    monkeypatch.setenv("XLA_FLAGS", f"{term}=5")
    ensure_cpu_mesh_flags()
    assert os.environ["XLA_FLAGS"].count(term) == 1
    assert f"{warn}=60" in os.environ["XLA_FLAGS"]


def _cache_dir_in_child(env_value):
    """(helper's return value, jax's configured directory) in a fresh
    interpreter — the helper updates process-global jax config."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    p = subprocess.run(
        [sys.executable, "-c",
         "from swiftmpi_tpu.utils.xla_env import ensure_compile_cache; "
         "import jax; print(ensure_compile_cache()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stderr
    return p.stdout.split()


def test_compile_cache_env_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the helper
    names no other directory."""
    placed = str(tmp_path / "placed")
    assert _cache_dir_in_child(placed) == [placed, placed]


def test_compile_cache_defaults_to_checkout():
    """Unset: <checkout>/.jax_cache, a fixed path."""
    from swiftmpi_tpu.utils.xla_env import REPO_ROOT

    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert REPO_ROOT == os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    assert _cache_dir_in_child(None) == [want, want]
