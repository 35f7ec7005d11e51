"""The word2vec step program owns its bookkeeping (ISSUE 44): the key's
split and the call's sums ride inside the jitted step, so a step is one
launch and a call's end one read.

(a) same draws: ``train()`` leaves the table, and the model's key, where
    the parent's loop — a host ``jax.random.split`` a step feeding the
    same step math — leaves them, bit for bit;
(b) one launch a step: what ``train()`` runs eagerly does not grow with
    the steps it trains;
(c) exact counts: the tally's counts are exact past 2^24, 2^31 and 2^32,
    its error sum within f32 summation error of the parent's fold;
(d) one form everywhere: a step rebuilt at a control safe point has the
    form the loop calls, in the async mode too.
"""

import collections
import math
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from swiftmpi_tpu.data.text import CBOWBatcher  # noqa: E402
from swiftmpi_tpu.models.word2vec import _Tally  # noqa: E402
from tests.test_w2v_stencil import corpus, make_model  # noqa: E402

BATCH = 24

#: (stencil, [section: overrides]) of each loop ``train()`` has
LOOPS = {
    "cbow_span": (1, {}),
    "cbow_per_pair": (0, {}),
    "skip_gram": (0, {"word2vec": {"sg": 1}}),
    "fused_group": (1, {"worker": {"inner_steps": 3}}),
    "fused_window": (0, {"worker": {"inner_steps": 4},
                         "cluster": {"push_window": 2}}),
    "async_snapshot": (1, {"word2vec": {"local_steps": 2}}),
}


def _parent_programs(model, fused):
    """The parent's programs, from the step math that stays: the sync step
    (grads against the state, pushed at once, the state donated), the
    async pair (grads against the stale snapshot, a program each) and the
    fused group, each handed the key the HOST split off."""
    grads_fn, apply_fn = model._build_grads(), model._build_apply()
    static = "centers" if model.stencil else None

    if model.local_steps > 1:
        grads, apply = jax.jit(grads_fn, static_argnames=static), \
            jax.jit(apply_fn)

        def step(state, frozen, statics, batch, sub, **shape):
            pushes, es, ec = grads(frozen, *statics, *batch, sub, **shape)
            return apply(state, pushes), es, ec
    else:
        @partial(jax.jit, donate_argnums=0, static_argnames=static)
        def sync(state, statics, batch, sub, **shape):
            pushes, es, ec = grads_fn(state, *statics, *batch, sub, **shape)
            return apply_fn(state, pushes), es, ec

        def step(state, _frozen, statics, batch, sub, **shape):
            return sync(state, statics, batch, sub, **shape)

    @partial(jax.jit, donate_argnums=0, static_argnames=static)
    def group(state, statics, batches, sub, **shape):
        n, W = batches[0].shape[0], model.push_window_size
        keys = jax.random.split(sub, n)
        if W == 1:
            def body(state, xs):
                *batch, k = xs
                pushes, es, ec = grads_fn(state, *statics, *batch, k,
                                          **shape)
                return apply_fn(state, pushes), (es, ec)
            state, (es, ec) = jax.lax.scan(body, state, (*batches, keys))
            return state, es.sum(), ec.sum()
        # push_window: a window's steps see its first state, and its
        # stacked pushes (held replicated) land in one exchange a family
        apply_window = model._build_apply_window()
        replicated = NamedSharding(model.cluster.mesh, P())
        es_tot, ec_tot = jnp.float32(0), jnp.float32(0)
        for s in range(0, n, W):
            def body(carry, xs):
                *batch, k = xs
                return carry, grads_fn(carry, *statics, *batch, k, **shape)
            _, (pushes_s, es, ec) = jax.lax.scan(
                body, state,
                (*(b[s:s + W] for b in batches), keys[s:s + W]))
            state = apply_window(state, jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, replicated),
                pushes_s))
            es_tot += es.sum()
            ec_tot += ec.sum()
        return state, es_tot, ec_tot

    return step, (group if fused else None)


def _parent_train(model, sents):
    """One epoch as the parent's ``train()`` ran it: ``key, sub =
    split(key)`` on the host before every dispatch."""
    model.build(sents)
    stencil = model._settle_stencil(None)      # as train(sentences) does
    batcher = CBOWBatcher(sents, model.vocab, model.window, model.sample,
                          seed=2008)
    fuse = model.inner_steps > 1 and model.local_steps <= 1
    step, group = _parent_programs(model, fuse)
    statics = (model._slot_of_vocab, model._alias_prob, model._alias_idx)
    shape = {"centers": BATCH} if stencil else {}
    key = model._key
    state = frozen = model.table.state
    n = 0
    for kind, fields, n_words in model._epoch_items(batcher, BATCH, stencil,
                                                    fuse):
        fields = tuple(jnp.asarray(f) for f in fields)
        if kind == "group" and len(n_words) == 1:
            kind, fields = "single", tuple(f[0] for f in fields)
        key, sub = jax.random.split(key)
        if kind == "group":
            state, _es, _ec = group(state, statics, fields, sub, **shape)
            continue
        state, _es, _ec = step(state, frozen, statics, fields, sub, **shape)
        n += 1
        if model.local_steps > 1 and n % model.local_steps == 0:
            frozen = state
    return state, key


@pytest.mark.parametrize("loop", list(LOOPS))
def test_train_draws_what_the_host_split_chain_drew(loop):
    stencil, overrides = LOOPS[loop]
    sents = corpus(n_sent=30, seed=5)
    model, parent = (make_model(stencil, **overrides) for _ in range(2))
    want, want_key = _parent_train(parent, sents)
    losses = model.train(sents, niters=1, batch_size=BATCH)
    assert np.isfinite(losses).all()
    got = model.table.state
    moved = False
    for f in want:
        a, b = np.asarray(got[f]), np.asarray(want[f])
        assert np.array_equal(a, b), (loop, f, np.abs(a - b).max())
        moved |= bool(np.any(b != 0))
    assert moved
    # the model's key is the host chain's: `families/w2v.py::_negatives`
    # (split(model._key)[1]) still predicts the next step's draw
    assert np.array_equal(jax.random.key_data(model._key),
                          jax.random.key_data(want_key))
    assert np.array_equal(
        jax.random.key_data(model.sampling_state()[0]),
        jax.random.key_data(jax.random.split(want_key)[1]))


class FirstBatches:
    """The first ``n`` batches of an epoch, in either rendering."""

    def __init__(self, inner, n):
        self.inner, self.n = inner, n

    def _first(self, epoch):
        for i, batch in enumerate(epoch):
            if i == self.n:
                return
            yield batch

    def epoch(self, batch_size):
        return self._first(self.inner.epoch(batch_size))

    def epoch_stencil(self, batch_size):
        return self._first(self.inner.epoch_stencil(batch_size))


#: eager primitives that run no program: a key wrapped around or read off
#: its data, a host batch put on the device (the loop's `h2d`)
NO_PROGRAM = {"random_wrap", "random_unwrap", "device_put"}


@pytest.mark.parametrize("loop", ["cbow_span", "skip_gram", "fused_group",
                                  "async_snapshot"])
def test_a_step_is_one_launch(loop, monkeypatch):
    """Programs ``train()`` launches: the step's own, once a dispatch, and
    a constant — nothing eager grows with the steps (the parent split the
    key and converted every returned scalar a step, and stacked and summed
    them a call: 7 launches a step more)."""
    from jax._src import core

    stencil, overrides = LOOPS[loop]
    sents = corpus(n_sent=100, seed=6)
    model = make_model(stencil, **overrides)
    model.build(sents)
    inner = CBOWBatcher(sents, model.vocab, model.window, model.sample,
                        seed=3)
    group = model.inner_steps if model.local_steps <= 1 else 1
    model.train(batcher=FirstBatches(inner, 2 * group), batch_size=BATCH)

    eager = collections.Counter()
    bind = core.EvalTrace.process_primitive

    def counting(self, primitive, tracers, params):
        eager[primitive.name] += 1
        return bind(self, primitive, tracers, params)

    monkeypatch.setattr(core.EvalTrace, "process_primitive", counting)
    launched = {}
    for steps in (4 * group, 12 * group):
        eager.clear()
        dispatched = model._steps_dispatched
        model.train(batcher=FirstBatches(inner, steps), batch_size=BATCH)
        assert model._steps_dispatched - dispatched == steps
        # a cached jit call never reaches the eval trace: what is counted
        # is everything else the loop ran
        launched[steps] = sum(n for name, n in eager.items()
                              if name not in NO_PROGRAM)
    assert launched[12 * group] == launched[4 * group] <= 4, (
        launched, dict(eager))


@pytest.mark.parametrize("count, steps", [
    (2 ** 20 + 1, 40),        # past 2^24: where an f32 fold stops counting
    (2 ** 27 + 3, 40),        # past 2^31: where an int32 wraps
    (2 ** 31 - 1, 9),         # past 2^32: the low limb's carry
])
def test_tally_counts_are_exact(count, steps):
    """A stub step adding large counts: the tally returns the Python-int
    sum exactly, every counter beside the pair count too."""

    @partial(jax.jit, donate_argnums=0)
    def stub(tally, n):
        return _Tally.add(tally, jnp.float32(0.5), n, n - 1, n // 3,
                          n // 5, n // 2, jnp.int32(7))

    tally = jnp.asarray(_Tally.zeros())
    for _ in range(steps):
        tally = stub(tally, jnp.int32(count))
    sums = _Tally.read(tally)
    assert sums["pairs"] == steps * count
    assert sums["rows"] == steps * (count - 1)
    assert sums["tiles"] == steps * (count // 3)
    assert sums["copies"] == steps * (count // 5)
    assert sums["routed"] == steps * (count // 2)
    assert sums["offered"] == steps * 7
    assert sums["pairs_weighted"] == 0.0
    assert sums["err"] == 0.5 * steps
    assert all(isinstance(sums[c], int) for c in _Tally.COUNTS)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["pair_count", "weighted_pairs"])
def test_tally_error_sum_within_f32_summation_error(weighted):
    """10,000 steps' error sums: the tally's compensated f32 sum against
    the exact sum and against the parent's fold (``jnp.stack(q).sum()``
    every 256, in f32); a weighted pair count (a float: the shared-pool
    renderings) takes the same path, an integer one stays out of it."""
    rng = np.random.default_rng(8)
    errs = (rng.random(10_000) * 3e4).astype(np.float32)
    pairs = rng.integers(5_000, 6_000, len(errs))
    ratio = np.float32(5 / 64)

    @jax.jit
    def run(errs, pairs):
        def body(tally, x):
            es, n = x
            return _Tally.add(tally, es, n * ratio if weighted else n), None
        return jax.lax.scan(body, jnp.asarray(_Tally.zeros()),
                            (errs, pairs))[0]

    sums = _Tally.read(run(jnp.asarray(errs), jnp.asarray(pairs, jnp.int32)))
    exact = math.fsum(errs.tolist())
    fold = []
    for e in errs:                     # the parent's `_LossAccum`
        fold.append(jnp.float32(e))
        if len(fold) >= 256:
            fold = [jnp.stack(fold).sum()]
    parent = float(jnp.stack(fold).sum())
    assert abs(parent - exact) <= 1e-5 * exact
    assert abs(sums["err"] - exact) <= 1e-7 * exact
    assert abs(sums["err"] - parent) <= 1e-5 * exact
    if weighted:
        want = math.fsum((pairs.astype(np.float32) * ratio).tolist())
        assert sums["pairs"] == 0
        assert abs(sums["pairs_weighted"] - want) <= 1e-7 * want
        assert sums["pair_count"] == round(want)
    else:
        assert sums["pairs"] == sums["pair_count"] == int(pairs.sum())
        assert sums["pairs_weighted"] == 0.0


@pytest.mark.parametrize("local_steps", [1, 2],
                         ids=["sync", "async_snapshot"])
def test_a_step_rebuilt_at_a_safe_point_carries_on(local_steps, devices8):
    """A control decision applied mid-epoch (`_apply_hot_k` ->
    `_rebuild_step`) leaves programs in the loop's form, in the async
    mode as in the sync one: training goes on, and the key is still the
    host-split chain's."""
    from swiftmpi_tpu.parameter.key_index import HotColdPartition
    from tests.test_control import _drift_model, _drift_setup

    _sents_a, sents_b, vocab = _drift_setup()
    model = _drift_model(word2vec={"local_steps": local_steps})
    model.build_from_vocab(vocab)
    assert model.table.n_hot > 0
    # the partition a hot_k decision moves to: phase B's frequencies
    freq = collections.Counter(w for row in sents_b for w in row)
    part_b = HotColdPartition.from_counts(
        vocab.keys, np.array([freq[int(k)] + 1 for k in vocab.keys],
                             np.int64), batch_rows=model.minibatch)
    assert part_b != model.table.key_index.partition
    applied = []

    def decide(n):
        """`_control_on_steps` with a controller that decides once."""
        if applied or model._steps_dispatched < 3:
            return False
        model._control_dirty = False
        assert model._apply_hot_k(part_b, {})
        applied.append(model._steps_dispatched)
        return model._control_dirty

    model._control_on_steps = decide
    key = model._key
    losses = model.train(sents_b, niters=1)
    assert applied == [3] and model._control_recompiles == 1
    assert model._steps_dispatched >= applied[0] + 3
    assert np.isfinite(losses).all()
    assert model.table.key_index.partition == part_b
    for _ in range(model._steps_dispatched):
        key = jax.random.split(key)[0]
    assert np.array_equal(jax.random.key_data(model._key),
                          jax.random.key_data(key))
