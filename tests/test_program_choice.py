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
