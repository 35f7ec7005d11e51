"""The tree alone decides the program a step runs (ISSUE 45).

A word2vec configuration of ``benchmark/configs/`` lowers the same train
step whatever lies around the checkout: a verdict file where
``ops/calibration.py`` used to read one, or any of the switches that
used to force a kernel or a rendering on.  The choice of a kernel lives
in the module that owns the operation (``XlaTransfer.write_back_form`` /
``route_mode``, ``Word2Vec.resolved_rendering``) and is made from static
shapes, the mesh and run-time counts, nothing else.
"""

import contextlib
import json
import os
from importlib import metadata

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = ("w2v-cbow-2m-300", "w2v-cbow-gnews-3m-300", "w2v-sg-2m-300")
# the toy of tests/test_benchmark_rehearsal.py::build_toy: a table the
# old VMEM gates would have said fits, and under dense_logits' 20,000 rows
V, LEN_VEC, MINIBATCH = 300, 16, 4096

OLD_KERNELS = ("vmem_gather", "vmem_scatter", "replica_scatter",
               "ring_push", "dense_logits")


def _verdicts():
    """A win for every old kernel under every device key the old gate
    could have asked for, stamped with this stack as `record` did."""
    import jaxlib

    # (the lookup is written out so that this file runs on the parent
    # commit too, where it has to fail)
    libtpu = "none"
    for dist in ("libtpu", "libtpu-nightly"):
        with contextlib.suppress(metadata.PackageNotFoundError):
            libtpu = metadata.version(dist)
            break
    stack = {"jaxlib": jaxlib.__version__, "libtpu": libtpu}
    kinds = {"TPU v5 lite", "TPU v5e", "tpu", "cpu", "interpret",
             jax.devices()[0].device_kind, jax.devices()[0].platform}
    return {f"{name}:{kind}": {"win": True, "R": 4, "idx_block": 256,
                               "stack": stack}
            for name in OLD_KERNELS for kind in kinds}


@contextlib.contextmanager
def _verdict_files(monkeypatch, tmp_path):
    """The verdicts where the old gate read them: at $SMTPU_CALIBRATION
    and at the checkout's own ``.bench_cache/calibration.json`` (put
    back as it was found)."""
    blob = json.dumps(_verdicts())
    elsewhere = tmp_path / "calibration.json"
    elsewhere.write_text(blob)
    monkeypatch.setenv("SMTPU_CALIBRATION", str(elsewhere))
    cache = os.path.join(REPO, ".bench_cache")
    in_repo = os.path.join(cache, "calibration.json")
    made_dir = not os.path.isdir(cache)
    kept = open(in_repo).read() if os.path.exists(in_repo) else None
    os.makedirs(cache, exist_ok=True)
    with open(in_repo, "w") as f:
        f.write(blob)
    try:
        yield
    finally:
        if kept is None:
            os.remove(in_repo)
            if made_dir:
                os.rmdir(cache)
        else:
            with open(in_repo, "w") as f:
                f.write(kept)


SWITCHES = ("SMTPU_CALIBRATION", "SMTPU_PALLAS_GATHER",
            "SMTPU_PALLAS_SCATTER", "SMTPU_DENSE_LOGITS", "SMTPU_RING_PUSH")
PLANTS = {
    "verdict_file": None,
    "pallas_gather": {"SMTPU_PALLAS_GATHER": "1"},
    "pallas_scatter": {"SMTPU_PALLAS_SCATTER": "1"},
    "dense_logits_ring_push": {"SMTPU_DENSE_LOGITS": "1",
                               "SMTPU_RING_PUSH": "1"},
}


def _conf(name):
    from swiftmpi_tpu.utils import ConfigParser

    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return config, ConfigParser().update({
        "word2vec": {**config["word2vec"], "len_vec": LEN_VEC},
        "server": dict(config["server"]),
        "worker": {"minibatch": MINIBATCH}})


def lowered_steps(conf, chips=1):
    """{rendering: lowered text} of every step a word2vec conf can run
    on ``chips`` devices: the one ``train()`` runs on the native batcher
    (spans for CBOW, ``sg`` for skip-gram) and, for CBOW, the per-pair
    step a batcher without spans gets."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.data.text import span_positions
    from swiftmpi_tpu.models.word2vec import Word2Vec, _Tally

    cluster = Cluster(conf, devices=jax.devices()[:chips]).initialize()
    model = Word2Vec(config=conf, cluster=cluster)
    # Word2Vec.build_from_vocab's capacity rule, and its rule for the
    # context side's rendering
    model.table = cluster.create_table(
        "w2v", model.access, max(64, int(V * 1.3 / cluster.n_servers) + 1))
    model._resolve_stencil()
    rep = NamedSharding(cluster.mesh, P())

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)

    centers, W2 = MINIBATCH // (2 * model.window), 2 * model.window
    key = jax.eval_shape(lambda: jax.random.key(0))
    tally = _Tally.zeros()

    def lowered(*batch, **statics):
        return model._build_step().lower(
            model.table.state, shape((V,), jnp.int32),
            shape((V,), jnp.float32), shape((V,), jnp.int32), *batch,
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep),
            shape(tally.shape, tally.dtype), **statics).as_text()

    texts = {}
    if model.stencil:
        span = span_positions(centers, model.window)
        texts["spans"] = lowered(
            shape((2 * span + 2 * centers,), jnp.int32), centers=centers)
        assert model.resolved_rendering == "stencil"
        model.stencil = 0
    texts["pairs"] = lowered(
        shape((centers,), jnp.int32), shape((centers, W2), jnp.int32),
        shape((centers, W2), jnp.bool_))
    assert model.resolved_rendering == ("sg" if model.sg else "gather")
    return texts


def step_texts(name):
    config, conf = _conf(name)
    texts = lowered_steps(conf, config["chips"])
    assert sorted(texts) == (["pairs"] if config["word2vec"]["sg"]
                             else ["pairs", "spans"])
    return texts


@pytest.fixture(scope="module")
def unplanted():
    texts = {}

    def get(name):
        if name not in texts:
            texts[name] = step_texts(name)
        return texts[name]
    return get


@pytest.mark.parametrize("plant", sorted(PLANTS))
@pytest.mark.parametrize("configuration", CONFIGS)
def test_step_text_ignores_files_and_environment(
        configuration, plant, unplanted, monkeypatch, tmp_path, devices8):
    for var in SWITCHES:
        monkeypatch.delenv(var, raising=False)
    want = unplanted(configuration)
    with contextlib.ExitStack() as planted:
        if PLANTS[plant] is None:
            planted.enter_context(_verdict_files(monkeypatch, tmp_path))
        else:
            for var, value in PLANTS[plant].items():
                monkeypatch.setenv(var, value)
        got = step_texts(configuration)
    assert got == want


# -- blockwise attention's forward walk (ISSUE 50) ----------------------------

@pytest.mark.parametrize("dtype,size,Hkv,G,D,kernel", [
    # the six LM cells' layers: the kernel takes them
    (jnp.bfloat16, 512, 4, 8, 128, True),       # sdar, trinity, keye2
    (jnp.bfloat16, 512, 8, 4, 64, True),        # lfm2: two heads a block
    (jnp.bfloat16, 512, 20, 1, 256, True),      # glm47f
    (jnp.bfloat16, 512, 2, 16, 128, True),      # nemotron3n
    (jnp.float32, 512, 4, 8, 128, True),
    (jnp.float32, 128, 8, 4, 64, True),         # the smallest tile
    (jnp.bfloat16, 256, 1, 1, 256, True),       # one head in all
    # what it does not take: the XLA walk on a TPU too
    (jnp.float16, 512, 4, 8, 128, False),       # dtype
    (jnp.bfloat16, 8, 4, 8, 128, False),        # a tile of 8 positions
    (jnp.bfloat16, 512, 4, 8, 96, False),       # no whole or half block
    (jnp.bfloat16, 512, 3, 4, 64, False),       # an odd head left over
    (jnp.bfloat16, 512, 1, 128, 128, False),    # more than VMEM holds
], ids=lambda x: getattr(x, "__name__", str(x)))
def test_attention_kernel_takes_by_dtype_and_shape(
        dtype, size, Hkv, G, D, kernel, monkeypatch):
    """``attention_kernel.takes`` — what ``ring_attention._forward`` asks
    before it offers the kernel to a TPU's lowering — is a function of its
    arguments: nothing in the environment moves it.  (The platform's half
    of the choice is ``lax.platform_dependent``'s: the next test.)"""
    import importlib
    ak = importlib.import_module("swiftmpi_tpu.parallel.attention_kernel")
    assert ak.takes(dtype, size, Hkv, G, D) == kernel
    for var in ("SMTPU_PALLAS_ATTENTION", "SMTPU_ATTENTION_KERNEL",
                "SMTPU_NO_PALLAS"):
        monkeypatch.setenv(var, "0" if kernel else "1")
    assert ak.takes(dtype, size, Hkv, G, D) == kernel


@pytest.mark.parametrize("platform,kernels", [("cpu", 0), ("tpu", 1)])
def test_attention_lowers_the_walk_of_the_platform_it_is_lowered_for(
        platform, kernels):
    """One traced call, two lowerings: the text for a TPU holds the
    kernel's custom call and no forward ``while``, the text for a CPU the
    XLA walk — whatever platform this process runs on."""
    import importlib
    ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")
    q = jax.ShapeDtypeStruct((1, 256, 8, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v: ra.blockwise_attention(
        q, k, v, block=128)).trace(q, k, k).lower(
            lowering_platforms=(platform,)).as_text()
    assert text.count("tpu_custom_call") == kernels
    assert ("attn_fwd_tiles" in text) == bool(kernels)
    assert ("stablehlo.while" in text) != bool(kernels)


def test_attention_kernel_is_traced_once_for_a_pass_and_its_recomputation(
        monkeypatch):
    """A rematerialised layer's forward pass and its recomputation reach
    ``attn_fwd_tiles`` under tracing contexts JAX tells apart (no abstract
    mesh, an empty one): the kernel's body — seconds of a run's set-up at
    the cells' sizes — is traced once for both all the same."""
    import importlib
    ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")
    ak = importlib.import_module("swiftmpi_tpu.parallel.attention_kernel")
    traced = []
    pairs = ak.tile_pairs
    monkeypatch.setattr(ak, "tile_pairs",
                        lambda *a: traced.append(a) or pairs(*a))
    q = jax.ShapeDtypeStruct((1, 384, 8, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 384, 2, 128), jnp.bfloat16)

    @jax.checkpoint
    def layer(q, k, v):
        return ra.blockwise_attention(q, k, v, block=128)

    def loss(q, k, v):
        return layer(layer(q, k, v), k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, k, k).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2
    assert len(traced) == 1, traced
