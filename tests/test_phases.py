"""Phase tracing inside the program (ISSUE 23): named scopes on the train
path, the phase map read off the compiled step (``obs.costs.phase_map``),
the host spans of a whole ``train()`` call, and the contract that none of
it changes the step: scopes are trace-time metadata.
"""

import contextlib
import logging

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from swiftmpi_tpu import obs  # noqa: E402
from swiftmpi_tpu.data.text import synthetic_corpus  # noqa: E402
from swiftmpi_tpu.models.word2vec import Word2Vec  # noqa: E402
from swiftmpi_tpu.obs import catalog  # noqa: E402
from swiftmpi_tpu.obs import costs as obs_costs  # noqa: E402
from swiftmpi_tpu.utils import ConfigParser  # noqa: E402

PHASES = ("sample", "pull", "math", "dedup", "apply")


def _corpus():
    return synthetic_corpus(40, vocab_size=60, length=14, seed=8)


def _cfg(transfer="xla", telemetry=True, w2v=None, worker=None):
    d = {
        "cluster": {"transfer": transfer},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2, **(w2v or {})},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512, **(worker or {})},
    }
    if telemetry:       # ring buffer only: no JSONL file
        d["worker"].update({"telemetry": 1, "telemetry_path": ""})
    return ConfigParser().update(d)


def _phase_samples():
    hists = obs.get_registry().snapshot()["hists"]
    return {k[len("phase_ms{phase="):-1]: v["count"]
            for k, v in hists.items() if k.startswith("phase_ms{")}


# -- the phase map of the compiled step --------------------------------------

@pytest.mark.parametrize("transfer,w2v,rendering", [
    ("xla", {}, "gather"),
    ("xla", {"shared_negatives": 1}, "shared"),
    ("xla", {"stencil": 1}, "stencil"),
    ("xla", {"sg": 1}, "sg"),
    ("tpu", {}, "gather"),
])
def test_phase_map_names_every_phase(transfer, w2v, rendering, devices8):
    m = Word2Vec(config=_cfg(transfer, w2v=w2v))
    m.train(_corpus(), niters=1, batch_size=64)
    assert m.resolved_rendering == rendering
    pm = obs_costs.phase_map("w2v_step")
    assert pm is not None
    assert pm["module"].startswith("jit_step")
    named = set(pm["phase"].values())
    assert set(PHASES) <= named, sorted(named)
    assert named <= set(catalog.DEVICE_SCOPES.values()) | {catalog.UNSCOPED}
    assert 0 <= pm["unscoped"] < pm["instructions"]
    # computed once per handle: the same object on the second ask
    assert obs_costs.phase_map("w2v_step") is pm
    assert obs_costs.phase_maps() == {pm["module"]: pm}


def test_phase_map_needs_telemetry_and_keeps_no_arrays():
    m = Word2Vec(config=_cfg(telemetry=False))
    m.train(_corpus(), niters=1, batch_size=64)
    assert obs_costs.get_catalog().idle
    assert m._step._sig is None          # no signature recorded
    assert obs_costs.phase_map("w2v_step") is None
    assert obs_costs.phase_maps() == {}

    obs.set_enabled(True)                # telemetry on; [obs] costs stays off
    assert not obs_costs.get_catalog().enabled
    m.train(_corpus(), niters=1, batch_size=64)
    args, kwargs = m._step._sig
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          for x in leaves)
    assert obs_costs.get_catalog().entries() == {}   # no compile events
    assert obs_costs.phase_map("w2v_step") is not None
    assert obs_costs.phase_map("no_such_program") is None


HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation (p0: f32[8,4], p1: s32[4]) -> f32[4,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[4]{0} parameter(1)
  %clamp.1 = s32[4]{0} clamp(%p1), metadata={op_name="jit(step)/jit(main)/math/pull/jit(_take)/clamp"}
  %gather.1 = f32[4,4]{1,0} gather(%p0, %clamp.1), metadata={op_name="jit(step)/jit(main)/math/pull/jit(_take)/gather"}
  ROOT %convert.1 = f32[4,4]{1,0} convert(%gather.1), metadata={op_name="jit(step)/jit(main)/math/convert_element_type"}
}

%fused_computation.1 (p0.1: f32[4,4]) -> f32[4,4] {
  %p0.1 = f32[4,4]{1,0} parameter(0)
  %tanh.1 = f32[4,4]{1,0} tanh(%p0.1), metadata={op_name="jit(step)/jit(main)/math/tanh"}
  ROOT %add.9 = f32[4,4]{1,0} add(%tanh.1, %tanh.1), metadata={op_name="jit(step)/jit(main)/window_dedup/add"}
}

%body (arg: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %arg = (s32[], f32[4,4]{1,0}) parameter(0)
  %gte.1 = f32[4,4]{1,0} get-tuple-element(%arg), index=1
  %sort.3 = f32[4,4]{1,0} sort(%gte.1), dimensions={0}, metadata={op_name="jit(step)/jit(main)/dedup/while/body/sort"}
  ROOT %tuple.2 = (s32[], f32[4,4]{1,0}) tuple(%gte.1, %sort.3)
}

ENTRY %main.42 (state: f32[8,4], idx: s32[4]) -> f32[8,4] {
  %state = f32[8,4]{1,0:T(8,128)} parameter(0), metadata={op_name="state"}
  %idx = s32[4]{0} parameter(1)
  %copy.141.remat3 = f32[8,4]{0,1:T(8,128)} copy(%state)
  %fusion.24 = f32[4,4]{1,0} fusion(%copy.141.remat3, %idx), kind=kLoop, calls=%fused_computation
  %fusion.25 = f32[4,4]{1,0} fusion(%fusion.24), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(main)/apply/mul"}
  %while.1 = (s32[], f32[4,4]{1,0}) while(%fusion.25), condition=%cond, body=%body, metadata={op_name="jit(step)/jit(main)/dedup/while"}
  %all-reduce.7 = f32[4,4]{1,0} all-reduce(%fusion.25), to_apply=%add, metadata={op_name="jit(step)/jit(main)/vmap(apply)/scatter"}
  ROOT %scatter.5 = f32[8,4]{1,0} scatter(%state, %idx, %all-reduce.7), metadata={op_name="jit(step)/jit(main)/apply/scatter"}
}
"""


def test_parse_hlo_phases_rules():
    pm = obs_costs.parse_hlo_phases(HLO)
    assert pm["module"] == "jit_step"
    ph = pm["phase"]
    # a fusion takes the phase most of its fused instructions carry —
    # innermost scope of each: two `pull`, one `math`
    assert ph["fusion.24"] == "pull"
    # a tie (math 1, window_dedup -> dedup 1) goes to the root's phase,
    # and the fusion's own op_name does not outvote its parts
    assert ph["fusion.25"] == "dedup"
    # a compiler-made copy with no metadata is under no scope: no phase
    # is guessed from its shape; rematerialised names are kept whole
    assert ph["copy.141.remat3"] == catalog.UNSCOPED
    # a while and the ops of its body each carry their own phase
    assert ph["while.1"] == "dedup" and ph["sort.3"] == "dedup"
    # a transform wraps the scope it ran under
    assert ph["all-reduce.7"] == "apply" and ph["scatter.5"] == "apply"
    # fused instructions are not trace events: not in the map
    assert "gather.1" not in ph and "tanh.1" not in ph
    # counts leave out parameters, tuples, get-tuple-elements
    assert pm["instructions"] == 7 and pm["unscoped"] == 1
    assert obs_costs.phase_of("jit(f)/serve/topk/top_k") is None


class _Stale:
    """A jit whose executable came from a compile cache written before the
    scopes existed: the cache key ignores metadata, so the traced program
    names the scopes and the compiled text does not."""

    def __init__(self, traced_text):
        self._traced = traced_text

    def lower(self, *a, **k):
        return self

    def compile(self):
        return self

    def as_text(self, debug_info=False):
        if debug_info:
            return self._traced
        return HLO.replace("/pull/", "/").replace("/math/", "/") \
            .replace("/window_dedup/", "/").replace("/dedup/", "/") \
            .replace("/apply/", "/").replace("vmap(apply)/", "")


@pytest.mark.parametrize("traced,level,needle", [
    ('loc("jit(step)/jit(main)/pull/gather")', logging.WARNING,
     "clear the cache"),
    ('loc("jit(step)/jit(main)/gather")', logging.INFO,
     "enters no known named scope"),
])
def test_phase_map_is_none_when_the_text_carries_no_phase(traced, level,
                                                          needle):
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger(obs_costs.__name__)
    logger.addHandler(handler)
    try:
        obs.set_enabled(True)
        f = obs_costs.track("stale_step", _Stale(traced))
        f._sig = ((), {})
        assert f.phase_map() is None       # not a map of all-unscoped
        assert f.phase_map() is None       # and computed (logged) once
    finally:
        logger.removeHandler(handler)
    assert [r.levelno for r in records] == [level]
    assert needle in records[0].getMessage()


def test_tracked_call_under_a_trace_records_nothing():
    obs.set_enabled(True)
    inner = obs_costs.track("inner_fn", jax.jit(lambda x: x * 2.0))

    @jax.jit
    def outer(x):
        return inner(x) + 1.0       # a tracked jit nested in another

    outer(jnp.ones((4,), jnp.float32))
    assert inner._sig is None
    inner(jnp.ones((4,), jnp.float32))
    assert inner._sig is not None


# -- host spans of the whole call ---------------------------------------------

@pytest.mark.parametrize("worker", [{}, {"pipeline": 2}],
                         ids=["inline", "pipeline2"])
def test_train_spans_one_sample_per_step_and_per_call(worker):
    m = Word2Vec(config=_cfg(worker=worker))
    m.train(_corpus(), niters=2, batch_size=64)
    got = _phase_samples()
    steps = int(obs.get_registry().snapshot()["counters"][
        "pipeline/consumed"]) if worker else got["dispatch"]
    assert steps > 2 and obs.get_recorder() is None
    for name in ("dispatch", "h2d", "input_wait"):
        assert got[name] == steps, (name, got)
    # per call; the epoch's fetch once per iteration of the call
    assert got["train_setup"] == 1 and got["train_finish"] == 1
    assert got["loss_fetch"] == 2
    m.train(_corpus(), niters=1, batch_size=64)
    got = _phase_samples()
    assert got["train_setup"] == 2 and got["train_finish"] == 2
    assert got["loss_fetch"] == 3
    assert set(got) <= set(catalog.HOST_SPANS)
    assert m.train_metrics["pipeline_depth"] == worker.get("pipeline", 0)


def test_span_with_attrs_off_is_the_shared_noop():
    assert not obs.get_registry().enabled
    null = obs.span("render")
    assert obs.span("x", step=3) is null
    with obs.span("dispatch", step=0, steps=4) as sp:
        sp.drop()
    obs.set_enabled(True)
    with obs.span("unit_dropped") as sp:
        sp.drop()
    with obs.span("unit_kept", step=3, steps=2):
        pass
    got = _phase_samples()
    assert got.get("unit_dropped", 0) == 0 and got["unit_kept"] == 1


# -- scopes change nothing ------------------------------------------------------

def _three_steps():
    m = Word2Vec(config=_cfg(telemetry=False))
    losses = m.train(_corpus()[:12], niters=1, batch_size=64)   # 3 steps
    return losses, {k: np.asarray(v) for k, v in m.table.state.items()}


def test_named_scopes_leave_the_step_bit_identical(monkeypatch):
    """The same loss and the same table, to the bit, from a build whose
    ``obs.named_scope`` is a null context: scopes are metadata."""
    l_scoped, t_scoped = _three_steps()
    obs.reset_for_tests()
    monkeypatch.setattr(obs, "named_scope",
                        lambda name: contextlib.nullcontext())
    l_plain, t_plain = _three_steps()
    assert l_scoped == l_plain
    for f in t_scoped:
        np.testing.assert_array_equal(t_scoped[f], t_plain[f])


# -- the negative sampler's slot lookups (ISSUE 25) --------------------------

def _sample_gather_rows(jaxpr):
    """Index rows of every gather under the ``sample`` scope, read off a
    jaxpr and the jaxprs nested in it."""
    rows = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "gather" and "sample"
                in str(eqn.source_info.name_stack).split("/")):
            rows.append(int(np.prod(eqn.invars[1].aval.shape[:-1])))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            rows += _sample_gather_rows(sub)
    return rows


def _trained_at(batch_size):
    m = Word2Vec(config=_cfg())
    m.train(synthetic_corpus(60, vocab_size=400, length=14, seed=8),
            niters=1, batch_size=batch_size)
    return m, m._alias_prob.shape[0], batch_size * m.negative


@pytest.mark.parametrize("batch_size,mode", [(16, "per_draw"),
                                             (128, "per_vocab")])
def test_sample_scope_gathers_over_the_vocabulary_only_from_V_draws_up(
        batch_size, mode):
    """The step of a batch that draws fewer negatives than the vocabulary
    has words holds no gather of V index rows under ``sample``
    (``slot_of_vocab[alias]``); one that draws more still builds it."""
    m, V, draws = _trained_at(batch_size)
    assert (draws < V) == (mode == "per_draw")
    args, kwargs = m._step._sig
    rows = _sample_gather_rows(
        jax.make_jaxpr(m._step._fn)(*args, **kwargs).jaxpr)
    assert draws in rows                    # the packed row of each draw
    assert (V in rows) == (mode == "per_vocab"), (V, rows)
    # per draw: the alias words' slots, one lookup a draw, in its place
    assert rows.count(draws) == (2 if mode == "per_draw" else 1)


@pytest.mark.parametrize("batch_size,mode", [(16, "per_draw"),
                                             (128, "per_vocab")])
def test_sampler_gauge_names_the_branch_the_step_took(batch_size, mode):
    _, V, draws = _trained_at(batch_size)
    gauges = {k: v for k, v in obs.get_registry().snapshot()["gauges"].items()
              if k.startswith("train/sampler_slot_lookups")}
    assert gauges == {f"train/sampler_slot_lookups{{mode={mode}}}":
                      draws if mode == "per_draw" else V}
