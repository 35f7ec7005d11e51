"""word2vec (CBOW + negative sampling) on the TPU parameter server.

Re-design of the reference word2vec apps — sync variant
(`/root/reference/src/apps/word2vec/word2vec.h`, used by w2v_local.cpp) and
async/global variant (`word2vec_global.h`, used by w2v.cpp) — as a single
model with a fused SPMD training step.

Reference hot loop (word2vec.h:550-615), per center word:
    b = rand % window;  context = +-(window-b) neighbors
    neu1 = sum of context input vectors v              (CBOW, raw sum)
    for target in {center (label 1), K negatives (label 0)}:
        skip negative if target == center
        f = neu1 . h_target
        g = (label - sigmoid_clipped(f)) * alpha       (ExpTable clip)
        error += 10000 * g^2                           (word2vec.h:593)
        h_grad[target] += g * neu1 ; neu1e += g * h_target
    v_grad[context_j] += neu1e  for each context word

Here the whole minibatch of that loop is one jitted step: padded
``(B, 2W)`` context matrices, ``(B, K)`` negatives drawn on device from the
alias-method unigram^0.75 sampler, gradients mean-normalized per key (the
reference's ``grad /= count`` at push serialization, word2vec.h:120-132),
pushed once through the transfer layer onto the row-sharded table with
server-side AdaGrad (word2vec.h:177-185).

Variant mapping (SURVEY.md §2.7): the reference's sync variant is this step
verbatim; its async/global variant (per-thread unsynchronized pull/push,
stale gradients, word2vec_global.h:577-651) maps to ``local_steps > 1`` —
gradients are computed against a table snapshot refreshed only every
``local_steps`` batches while pushes land immediately, reproducing
bounded-staleness async SGD without abandoning SPMD.

Skip-gram mode (``[word2vec] sg: 1`` — the BASELINE.md config-#2 text8
benchmark): each (context, center) pair is an independent example — input
vector v[context word], targets h[center] (label 1) + K fresh negatives per
pair (label 0), exactly the word2vec.c skip-gram loop the reference's CBOW
hot loop was derived from.  Same batch layout; the pair axis is (B, 2W).
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from swiftmpi_tpu import obs
from swiftmpi_tpu.cluster.cluster import Cluster
from swiftmpi_tpu.data.text import (CBOWBatcher, Vocab, build_vocab,
                                    load_corpus,  # noqa: F401 (Vocab: API)
                                    unpack_span)
from swiftmpi_tpu.io.checkpoint import dump_table_text, load_table_text
from swiftmpi_tpu.ops.sampling import (alias_slot_lookups,
                                       build_unigram_alias, sample_alias,
                                       sample_alias_slots)
from swiftmpi_tpu.ops.sigmoid import sigmoid_clipped
from swiftmpi_tpu.parameter import w2v_access
from swiftmpi_tpu.testing import faults
from swiftmpi_tpu.transfer import PushSpec
from swiftmpi_tpu.utils.config import ConfigParser, global_config
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.pipeline import (DispatchWindow,
                                         resolve_dispatch_bound)
from swiftmpi_tpu.utils.timers import Throughput

log = get_logger(__name__)


def _dev(x):
    """Batch arg -> device: distributed batches arrive as global
    jax.Arrays whose sharding must be left alone (jnp.asarray would
    re-place them); host arrays go through jnp.asarray."""
    return x if isinstance(x, jax.Array) else jnp.asarray(x)


class _Tally:
    """The sums a train call reads once, carried by the step program.

    One ``uint32[2, 8]`` device array.  Every step program takes it
    (donated, like the table state) and returns it with its own sums
    added, so a step is one launch and a call's end one wait and one
    read: no per-step convert, no stack-and-sum at the fetch.  Columns:

    - ``err``, ``pairs_weighted``: float sums, each the bits of an f32
      Kahan pair (row 0 the running sum, row 1 its compensation) — the
      error sum (word2vec.h:593), and the pair count of the renderings
      that weigh a pooled negative as a fraction of a pair
      (``shared_negatives``).  The compensation keeps an epoch-long
      call's sum within a few ulps of the exact one.
    - ``pairs``, ``rows``, ``tiles``, ``copies``, ``routed``, ``offered``:
      counts, each a uint32 (low, high) limb pair: row 0 wraps at 2^32
      and carries into row 1, so a count is EXACT to 2^64 — a bare int32
      would wrap at 2.1e9 pairs, the corpus sizes a call an epoch long is
      for, and an f32 is exact only to 2^24.  ``rows`` / ``tiles`` /
      ``copies`` / ``routed`` / ``offered`` are what a step built with
      telemetry on counts
      (`_build_step`'s ``counted``); they stay 0 otherwise.
    """

    FLOATS = ("err", "pairs_weighted")
    COUNTS = ("pairs", "rows", "tiles", "copies", "routed", "offered")

    @classmethod
    def zeros(cls) -> np.ndarray:
        return np.zeros((2, len(cls.FLOATS) + len(cls.COUNTS)), np.uint32)

    @classmethod
    def add(cls, tally, es, ec, *counts):
        """``tally`` plus one dispatch's sums, inside the step program:
        ``es`` the error sum, ``ec`` the pair count (an integer, or a
        float where negatives are weighed), ``counts`` the telemetry
        counters in ``COUNTS[1:]``'s order.

        ``tally=None`` starts a fresh one in the program.  No loop of
        this module passes it: it is there so a caller that lowers or
        drives a step by hand with the arguments it had before the step
        carried a tally — ``benchmark/tools/compile_real_size.py``, which
        this module's PRs may not edit, and a few tests — still gets the
        step's program."""
        f32, u32 = jnp.float32, jnp.uint32
        nf, n = len(cls.FLOATS), len(cls.FLOATS) + len(cls.COUNTS)
        if tally is None:
            tally = jnp.zeros((2, n), u32)

        def bits(x, to=u32):
            return jax.lax.bitcast_convert_type(x, to)

        weighted = jnp.issubdtype(jnp.result_type(ec), jnp.floating)
        x = [bits(jnp.asarray(es, f32)),
             bits(jnp.asarray(ec if weighted else 0, f32)),
             *(jnp.asarray(k).astype(u32)
               for k in (0 if weighted else ec, *counts))]
        x = jnp.stack(x + [u32(0)] * (n - len(x)))
        lo, hi = tally
        # every column both ways, in one pass over the eight lanes, and
        # each keeps its own: Kahan's sum and compensation ...
        xf, s, c = bits(x, f32), bits(lo, f32), bits(hi, f32)
        y = xf - c
        t = s + y
        # ... or the low limb's add and its carry
        low = lo + x
        is_float = jnp.arange(n) < nf
        return jnp.stack([
            jnp.where(is_float, bits(t), low),
            jnp.where(is_float, bits((t - s) - y),
                      hi + (low < lo).astype(u32))])

    @classmethod
    def read(cls, tally) -> dict:
        """The call's one device-to-host read: ``{column: sum}``, floats
        as Python floats, counts as Python ints — and ``pair_count``,
        the loss's denominator: the pairs counted plus the weighted ones,
        rounded."""
        t = np.asarray(tally)
        nf = len(cls.FLOATS)
        s, c = t[:, :nf].view(np.float32).astype(np.float64)
        out = dict(zip(cls.FLOATS, (s - c).tolist()))
        lo, hi = t[:, nf:].tolist()
        out.update(zip(cls.COUNTS, ((h << 32) | l for l, h in zip(lo, hi))))
        out["pair_count"] = out["pairs"] + int(round(out["pairs_weighted"]))
        return out


def _carried(key, tally, run):
    """The bookkeeping a step program owns.  ``key`` is the MODEL's key:
    the program splits it as ``train()`` used to on the host — the draws
    are that sequence bit for bit — runs ``run(sub) -> (out, es, ec,
    *counts)`` and returns ``(out, next key, tally, es)``: the next key as
    its data (the caller wraps it, `Word2Vec._rekey`: a typed key's
    stored sharding reads ``P()`` and ``P(None)`` by turns across a jit
    boundary on a mesh, and every turn would be one more entry in the
    step's dispatch cache), the tally with this dispatch's sums added
    (`_Tally.add`), and ``es`` once more on its own, the one result that
    is not donated into the next step: what a `DispatchWindow` waits on."""
    key, sub = jax.random.split(key)
    out, es, ec, *counts = run(sub)
    return out, jax.random.key_data(key), _Tally.add(tally, es, ec,
                                                     *counts), es


class _PairCount:
    """Valid (center, context) pairs against the ``(B, 2W)`` pair grid a
    step gathers and pushes, counted from the host batches as they are
    rendered (``_epoch_items``: before ``h2d``, no device work).  Exists
    only with telemetry on; feeds ``train/pairs{kind=valid|grid}`` and
    ``train_metrics``' ``pairs_per_step`` / ``pair_fill_share``.  For
    CBOW a pair is one summed context.  A span batch is counted from its
    positions (``observe_span``): the same valid pairs against the same
    ``(B, 2W)`` grid, which the span step never builds, and the span's
    positions, which it pulls and pushes instead (``span_rows``).
    Multi-process batches are already-placed global arrays and are not
    counted; a run of only such batches exports no ``train/pairs``
    series at all (the counters are made on the first counted batch), so
    a series that reads 0 means no valid pair, never "not counted"."""

    def __init__(self, reg):
        self._reg = reg
        self._valid = self._grid = None
        self.valid = self.grid = self.steps = self.span_rows = 0

    def _count(self, valid: int, grid: int, steps: int) -> None:
        if self._valid is None:
            self._valid = self._reg.counter("train/pairs", kind="valid")
            self._grid = self._reg.counter("train/pairs", kind="grid")
        self.valid += valid
        self.grid += grid
        self.steps += steps
        self._valid.inc(valid)
        self._grid.inc(grid)

    def observe(self, ctx_mask) -> None:
        """``ctx_mask``: ``(B, 2W)`` of one step or ``(L, B, 2W)`` of a
        fused group."""
        if not isinstance(ctx_mask, np.ndarray):
            return
        self._count(int(np.count_nonzero(ctx_mask)), ctx_mask.size,
                    1 if ctx_mask.ndim == 2 else ctx_mask.shape[0])

    def observe_span(self, packed, centers: int, window: int) -> None:
        """``packed``: ``(2S + 2B,)`` of one step or ``(L, 2S + 2B)`` of
        a fused group (`StencilBatch.pack`).  A center's valid pairs are
        the positions within its half window, in the span and of its
        sentence — `stencil_to_cbow`'s expansion, counted not built: a
        sentence is one run of equal ids in the span (the wire format's
        contiguous slice of the stream), so a window reaches
        ``min(half, distance to the run's end)`` on either side."""
        if not isinstance(packed, np.ndarray):
            return
        packed = packed.reshape(-1, packed.shape[-1])
        valid = 0
        for tokens, sent_id, center_pos, half in (
                unpack_span(row, centers) for row in packed):
            S = len(tokens)
            starts = np.flatnonzero(np.diff(sent_id, prepend=-2))
            ends = np.append(starts[1:], S)       # one past each run
            live = center_pos >= 0
            cp, hf = center_pos[live], half[live]
            run = np.searchsorted(starts, cp, side="right") - 1
            valid += int(np.minimum(hf, cp - starts[run]).sum()
                         + np.minimum(hf, ends[run] - 1 - cp).sum())
            self.span_rows += S
        self._count(valid, len(packed) * centers * 2 * window, len(packed))


def _stack_group_host(batches):
    """Stack a group of same-shape batches host-side (one contiguous H2D
    transfer per field, not one per batch).  Pure numpy — this is the
    rendering work the input pipeline's producer thread runs off the
    critical path."""
    return (np.stack([np.asarray(b.centers) for b in batches]),
            np.stack([np.asarray(b.contexts) for b in batches]),
            np.stack([np.asarray(b.ctx_mask) for b in batches]))


def _stack_group_host_stencil(batches):
    """StencilBatch variant of ``_stack_group_host``: the packed spans,
    one row a batch.  Every stencil batch is fixed-shape (span and
    center arrays are padded, only ``n_words`` varies), so even epoch
    tails stack and fuse."""
    return (np.stack([b.pack() for b in batches]),)


def _stack_group(batches):
    return tuple(jnp.asarray(f) for f in _stack_group_host(batches))


def _negative_slots(key, alias_prob, alias_idx, slot_of_vocab, shape,
                    take=None):
    """``sample_alias_slots`` for a train step.  While the step is
    traced (Python time: nothing enters the program) the gauge
    ``train/sampler_slot_lookups{mode=}`` takes the scalar slot lookups
    the program makes for its negatives, under the branch it took."""
    reg = obs.get_registry()
    if reg.enabled:
        mode, lookups = alias_slot_lookups(alias_prob.shape[0], shape)
        reg.gauge("train/sampler_slot_lookups", mode=mode).set(lookups)
    return sample_alias_slots(key, alias_prob, alias_idx, slot_of_vocab,
                              shape, take)


def _cbow_targets(slot_of_vocab, alias_prob, alias_idx, centers,
                  contexts, ctx_mask, key, K):
    """Shared CBOW batch layout: draw the negatives and build the
    target/context slot matrices + validity masks."""
    with obs.named_scope("sample"):
        B = centers.shape[0]
        # fused draw: negatives and their table slots from ONE packed row
        # gather a draw (see ops/sampling.sample_alias_slots)
        negs, neg_slots = _negative_slots(
            key, alias_prob, alias_idx, slot_of_vocab, (B, K))
        t_slots = jnp.concatenate(
            [slot_of_vocab[centers][:, None], neg_slots], axis=1)  # (B, K+1)
        ctx_slots = jnp.where(ctx_mask, slot_of_vocab[contexts], -1)
        row_valid = ctx_mask.any(axis=1)
        # negative == center is skipped (word2vec.h:584-586)
        t_valid = jnp.concatenate(
            [jnp.ones((B, 1), bool), negs != centers[:, None]], axis=1)
        t_valid = t_valid & row_valid[:, None]
    return t_slots, ctx_slots, t_valid


def _assemble_push(tf, cf, h_flat, v_flat):
    """Lay out one push per gradient family: h-grads keyed by target
    slots, v-grads keyed by context slots, both with ``mean=True`` — the
    reference's per-key grad/count normalization (word2vec.h:120-132)
    now happens inside the transfer's own dedup pass, where the counts
    come free with the segment/scatter sums.  (Round 1 concatenated both
    families into a single zero-padded batch — which doubled every
    downstream push array; round 2's worker-side pre-scaling cost a
    capacity scatter + batch gather + (B, d) multiply per family, ~25%
    of the measured step — both folded away here.)  Per-family pushes
    carry only real contributions; apply_push handles partial grad
    dicts.

    The two pushes write disjoint fields, so their order moves no value;
    it moves the step's peak memory (PERF.md section 6, PR 34).  The
    v-grads sum over a center's targets, the last thing the math
    computes: pushed first, each push's batch work follows the math push
    by push.  With h first the compiler sorts and sums the h batch while
    the contexts' rows are still to be pulled, one ``(B, width)`` buffer
    more at the peak (0.25 GB on the chip in ``cbow2m-b16k``;
    ``tests/test_compile_v5e.py`` bounds the temporaries)."""
    return (PushSpec(cf, {"v": v_flat}, mean=True),
            PushSpec(tf, {"h": h_flat}, mean=True))


def w2v_formatter(row: Dict[str, np.ndarray]) -> str:
    """Reference WParam operator<< layout: v-vector TAB h-vector
    (word2vec.h:100-110)."""
    v = " ".join(repr(float(x)) for x in row["v"])
    h = " ".join(repr(float(x)) for x in row["h"])
    return f"{v}\t{h}"


def w2v_parser(text: str) -> Dict[str, np.ndarray]:
    v_s, _, h_s = text.partition("\t")
    return {"v": np.array([float(x) for x in v_s.split()], np.float32),
            "h": np.array([float(x) for x in h_s.split()], np.float32)}


class Word2Vec:
    def __init__(self, config: Optional[ConfigParser] = None,
                 cluster: Optional[Cluster] = None,
                 capacity_per_shard: Optional[int] = None, seed: int = 0):
        self.config = config if config is not None else global_config()
        g = self.config.get_or
        self.len_vec = g("word2vec", "len_vec", 100).to_int32()
        self.window = g("word2vec", "window", 4).to_int32()
        self.negative = g("word2vec", "negative", 20).to_int32()
        self.sample = g("word2vec", "sample", -1.0).to_float()
        self.sg = g("word2vec", "sg", 0).to_int32()
        # TPU-first opt-in: one pool of negatives shared by the whole
        # batch (see _build_grads_shared) instead of the reference's
        # per-center draws.  Pool size defaults to 1024: sharing K-per-
        # center-sized pools starves the negative phase (each vocab word
        # is drawn ~B-times less often per epoch).
        self.shared_negatives = g(
            "word2vec", "shared_negatives", 0).to_int32()
        self.shared_pool = g("word2vec", "shared_pool", 1024).to_int32()
        # Positional-stencil rendering — the batcher emits stream
        # POSITIONS over a span that holds the batch's centers and the
        # step pulls, window-sums and pushes each position once (S rows
        # instead of B·2W context rows), by statically shifted sums with
        # sentence-boundary masks.  Composes with shared_negatives for
        # the pool-negative h side.  See _build_grads_stencil.  1 asks
        # for it; left at 0 a CBOW model that can take spans resolves to
        # it at build time (_resolve_stencil) and train() keeps it where
        # the batcher renders spans.
        self.stencil = g("word2vec", "stencil", 0).to_int32()
        #: the rendering was resolved from what the model observes, not
        #: asked for: train() may still fall back to per-pair batches
        self._stencil_auto = False
        self.alpha = g("word2vec", "learning_rate", 0.05).to_float()
        self.min_sentence_length = g(
            "word2vec", "min_sentence_length", 1).to_int32()
        self.minibatch = g("worker", "minibatch", 5000).to_int32()
        # [worker] inner_steps: fuse N sync steps per dispatch via
        # lax.scan (amortizes per-dispatch overhead, not measured on
        # this stack).  Default 1 = exactly one dispatch per batch.
        self.inner_steps = g("worker", "inner_steps", 1).to_int32()
        # [cluster] push_window: coalesce W consecutive steps' pushes
        # into ONE exchange per push family (transfer.push_window).
        # Gradients inside a window are computed against window-start
        # state, so staleness is bounded by W-1 steps; W=1 (default)
        # keeps the per-step path bit-identically.  Only meaningful on
        # the fused (inner_steps > 1) sync path.
        self.push_window_size = g("cluster", "push_window", 1).to_int32()
        if self.push_window_size < 1:
            raise ValueError("[cluster] push_window must be >= 1")
        # [cluster] wire_quant: off|int8|bf16 — value quantization for
        # the window push's sparse wire formats.  Arms the 4-way
        # dense/sparse/bitmap/sparse_q crossover on the transfer and the
        # per-field @ef error-feedback residual planes on the table
        # (quantization error banks worker-side and drains into the next
        # quantized window, so the trajectory tracks the f32 wire within
        # the documented envelope).  "off" (default) keeps the 2-way
        # decision and the wire bit-identical to the pre-quantization
        # path.  Only meaningful with push_window > 1.
        self.wire_quant = g("cluster", "wire_quant", "off").to_string()
        if self.wire_quant not in ("off", "int8", "bf16"):
            raise ValueError("[cluster] wire_quant must be off, int8 or "
                             f"bf16, got {self.wire_quant!r}")
        # [cluster] pull_quant: off|int8|bf16 — wire quantization for
        # the PULL family (transfer/plan.py price_pull_formats).  The
        # dequantized read perturbs the forward pass only — server
        # state is never written through a quantizer, so no EF plane is
        # involved and the PR-10 trajectory envelope applies.  "off"
        # (default) keeps pulls bit-identical to the f32 wire.
        self.pull_quant = g("cluster", "pull_quant", "off").to_string()
        if self.pull_quant not in ("off", "int8", "bf16"):
            raise ValueError("[cluster] pull_quant must be off, int8 or "
                             f"bf16, got {self.pull_quant!r}")
        # [cluster] pull_cache: N > 0 arms the worker-side versioned
        # pull cache with N direct-mapped lines (transfer/pull_cache.py)
        # and the table's @rowver stamp plane.  Version-exact hits ship
        # zero value bytes (watermark + hit bitmap only); the ledger's
        # pull_bytes drops accordingly.  0 (default) keeps the table
        # state pytree and the pull ledger bit-identical.
        self.pull_cache = g("cluster", "pull_cache", 0).to_int32()
        if self.pull_cache < 0:
            raise ValueError("[cluster] pull_cache must be >= 0, got "
                             f"{self.pull_cache!r}")
        # [cluster] wire_sketch: 0|1 — admit the counting-sketch index
        # rung (sparse_sketch: bucketed uint16 counts + uint8 in-bucket
        # offsets instead of i32 indices) to the window wire-format
        # crossover.  Lossless and EF-compatible; the TrafficPlan pricer
        # (parameter/key_index.py) still picks per family, so arming the
        # knob only changes the wire where the sketch byte model wins.
        # Only meaningful with push_window > 1.
        self.wire_sketch = g("cluster", "wire_sketch", 0).to_int32()
        if self.wire_sketch not in (0, 1):
            raise ValueError("[cluster] wire_sketch must be 0 or 1, got "
                             f"{self.wire_sketch!r}")
        # [cluster] collective: psum|auto|sparse_allreduce — collective
        # selection for the dense/hot reconcile planes (transfer/
        # sparse_allreduce.py).  "psum" (default) keeps the legacy dense
        # collectives bit-identically; "auto" prices the Ok-Topk sparse
        # split-and-exchange against the dense psum per plan from the
        # live hot-touch density (seeded from the vocab histogram,
        # retuned by the Controller); "sparse_allreduce" pins it.
        # Only meaningful on the hybrid/tpu window paths.
        self.collective_mode = g("cluster", "collective",
                                 "psum").to_string()
        from swiftmpi_tpu.transfer.plan import COLLECTIVE_MODES
        if self.collective_mode not in COLLECTIVE_MODES:
            raise ValueError("[cluster] collective must be one of "
                             f"{COLLECTIVE_MODES}, got "
                             f"{self.collective_mode!r}")
        # [worker] pipeline: K > 0 turns on the asynchronous input
        # pipeline (io/pipeline.py) — a producer thread renders batches
        # K ahead and eagerly device_puts them so H2D overlaps compute.
        # 0 (default) keeps the synchronous loop bit-identically: the
        # producer owns no RNG and preserves batch order, so K only
        # changes WHEN work happens, never what is computed.
        self.pipeline_depth = g("worker", "pipeline", 0).to_int32()
        if self.pipeline_depth < 0:
            raise ValueError("[worker] pipeline must be >= 0")
        # [worker] dispatch_depth: in-flight dispatch watermark
        # (utils.pipeline.resolve_dispatch_bound).  "auto" = backend
        # policy, tightened to a finite bound whenever the pipeline is
        # on; an integer forces it; 0 = unbounded.
        self.dispatch_depth = g("worker", "dispatch_depth",
                                "auto").to_string()
        self.local_steps = g("word2vec", "local_steps", 1).to_int32()
        # "" /"snapshot" (bounded-staleness via local_steps) / "hogwild"
        # (genuinely unsynchronized per-device replicas, see
        # _build_hogwild_step)
        self.async_mode = g("word2vec", "async_mode", "").to_string()
        server_lr = g("server", "initial_learning_rate", 0.7).to_float()
        # [server] dtype: bfloat16 halves the embedding fields' HBM
        # gather/scatter bytes (the measured TPU bottleneck); math stays
        # fp32 (upcast on pull, round once on store), accumulators fp32
        dtype_s = g("server", "dtype", "float32").to_string()
        if dtype_s not in ("float32", "bfloat16"):
            raise ValueError(f"[server] dtype must be float32 or "
                             f"bfloat16, got {dtype_s!r}")
        self.param_dtype = jnp.bfloat16 if dtype_s == "bfloat16" \
            else jnp.float32

        # [serve] every: publish a bounded-staleness serving snapshot of
        # the table every N consumed train steps (serve/snapshot.py);
        # 0 (default) = serving plane off.  [serve] depth bounds how many
        # published generations the publisher itself keeps referenced.
        self.serve_every = g("serve", "every", 0).to_int32()
        self.serve_depth = g("serve", "depth", 2).to_int32()
        if self.serve_every < 0:
            raise ValueError("[serve] every must be >= 0")
        self.serve_publisher = None

        # [control] (control/): the adaptive control plane — re-derive
        # hot_k / push_window / wire-format knobs online from the live
        # traffic ledger and the decayed id-frequency sketch.  Off (the
        # default) constructs NOTHING: no sketch, no controller, no
        # observation — trajectories are bit-identical to a build
        # without the plane (the tests pin this down).
        from swiftmpi_tpu.control import ControlSettings
        self.control_settings = ControlSettings.from_config(self.config)
        self.controller = None
        self._control_sketch = None
        self._control_recompiles = 0
        self._control_dirty = False

        # [obs] numerics: the training-numerics health plane (ISSUE 13,
        # obs/numerics.py).  Off (the default) constructs NOTHING and
        # traces NOTHING extra into the step — trajectories are
        # bit-identical to a build without the plane; on, the fused
        # step ships a fixed-cost bundle (grad norms, update/param
        # ratio, EF residual mass, quant error, nonfinite counts) to a
        # host collector + anomaly detector armed in train().
        from swiftmpi_tpu.obs import numerics as obs_numerics
        self.numerics_on = obs_numerics.enabled(self.config)
        self._numerics: Optional[obs_numerics.NumericsCollector] = None
        self._numerics_restore = None   # checkpointed baseline bytes
        self._numerics_rec_id: Optional[int] = None

        self.cluster = cluster or Cluster(self.config).initialize()
        self.access = w2v_access(server_lr, self.len_vec,
                                 param_dtype=self.param_dtype)
        #: lanes of a stored row (`access.stored_width`): what the step
        #: pulls, multiplies and pushes; the first `len_vec` are the
        #: vector, the rest stay zero
        self.row_width = self.access.fields["h"].dim
        self._capacity_per_shard = capacity_per_shard
        self.table = None
        self.transfer = self.cluster.transfer
        self.vocab: Optional[Vocab] = None
        self._step = None
        self._fused_cache = {}
        #: train steps dispatched by this model, over all train() calls:
        #: the `dispatch` span's step= (a fused group carries steps=L)
        self._steps_dispatched = 0
        #: the sampler's key: a step program takes it and returns the
        #: next (`_carried`), so between steps it is a device value that
        #: `train()` repoints like `table.state` — the array a caller read
        #: before a step is donated into that step
        self._key = jax.random.key(seed ^ 0x5EED)
        self._key_impl = jax.random.key_impl(self._key)
        # per-train() observability: hogwild tail-skip count, hybrid
        # transfer traffic counters — refreshed by every train() call
        self.train_metrics: dict = {}

    # -- vocab / table bring-up (word2vec_global.h:385-444) ----------------
    def build(self, sentences) -> "Word2Vec":
        return self.build_from_vocab(build_vocab(sentences))

    def build_from_vocab(self, vocab: Vocab) -> "Word2Vec":
        """Bring up table + sampler from a prebuilt vocab (e.g. the native
        C++ loader's) without a python counting pass.  Set-up spans
        (``obs.catalog.SETUP_SPANS``): ``model_build`` over the whole of
        it, with the children ``table_create`` (``Cluster.create_table``),
        ``key_index`` and ``sampler_build``."""
        with obs.setup_span("model_build"):
            return self._bring_up(vocab)

    def _bring_up(self, vocab: Vocab) -> "Word2Vec":
        self.vocab = vocab
        V = len(self.vocab)
        if V == 0:
            raise ValueError(
                "empty vocabulary — no sentence survived loading; check the "
                "corpus and [word2vec] min_sentence_length")
        if self.table is None:
            cap = self._capacity_per_shard or max(
                64, int(V * 1.3 / self.cluster.n_servers) + 1)
            partition = None
            if getattr(self.transfer, "name", "") == "hybrid":
                # Zipf-aware hot/cold split: replicate the measured
                # frequency head, shard the tail (transfer/hybrid.py).
                # batch_rows drives the dense-vs-sparse crossover in the
                # calibration: the head pays off while its dense psum
                # stays comparable to the head hits a batch routes.
                from swiftmpi_tpu.parameter.key_index import \
                    HotColdPartition
                partition = HotColdPartition.from_counts(
                    self.vocab.keys, self.vocab.counts,
                    batch_rows=self.minibatch)
                log.info(
                    "hybrid placement: %d hot keys (%.1f%% of token "
                    "mass) replicated; %d tail keys sharded",
                    partition.n_hot, 100 * (partition.head_mass or 0.0),
                    V - partition.n_hot)
            self.table = self.cluster.create_table(
                "w2v", self.access, cap, partition=partition)
        with obs.setup_span("key_index"):
            slots = self.table.key_index.lookup(self.vocab.keys)
            self._slot_of_vocab = jnp.asarray(slots, jnp.int32)
        if self.push_window_size > 1 and hasattr(
                self.transfer, "window_expected_unique"):
            # sharpen the per-window sparse/dense wire-format crossover
            # with the Zipf-aware expected unique-row count of a window's
            # worth of token draws (cluster.hashfrag.expected_unique_rows)
            from swiftmpi_tpu.cluster.hashfrag import expected_unique_rows
            self.transfer.window_expected_unique = expected_unique_rows(
                self.vocab.counts,
                self.push_window_size * self.minibatch)
        if self.wire_quant != "off":
            if self.push_window_size > 1:
                self.transfer.wire_quant = self.wire_quant
                # EF residual planes for every window-pushed grad family
                # — created BEFORE any step compiles so the state pytree
                # shape is stable for the fused scan and checkpoints
                self.table.ensure_ef(tuple(self.access.grad_fields))
            else:
                log.warning(
                    "[cluster] wire_quant: %s has no effect at "
                    "push_window: 1 (per-step pushes ship f32); "
                    "ignoring", self.wire_quant)
        if self.wire_sketch:
            if self.push_window_size > 1 and hasattr(
                    self.transfer, "wire_sketch"):
                self.transfer.wire_sketch = True
            else:
                log.warning(
                    "[cluster] wire_sketch has no effect at "
                    "push_window: 1 (per-step pushes ship indexed "
                    "rows); ignoring")
        if self.collective_mode != "psum":
            if self.push_window_size > 1 and hasattr(
                    self.transfer, "collective_mode"):
                self.transfer.collective_mode = self.collective_mode
                self.transfer.hot_touched_fraction = \
                    self._seed_hot_touched_fraction()
                log.info(
                    "[cluster] collective: %s armed (seed hot-touch "
                    "density %.4f)", self.collective_mode,
                    self.transfer.hot_touched_fraction or 0.0)
            else:
                log.warning(
                    "[cluster] collective: %s has no effect at "
                    "push_window: 1 (the per-step hot psum is not "
                    "plan-compiled); ignoring", self.collective_mode)
        if self.pull_quant != "off":
            # unlike the push-side knobs, pulls happen every step at
            # any window size — no push_window gate
            self.transfer.pull_quant = self.pull_quant
            log.info("[cluster] pull_quant: %s armed", self.pull_quant)
        if self.pull_cache:
            self.transfer.pull_cache = int(self.pull_cache)
            # the @rowver plane the watermark protocol reads — created
            # BEFORE any step compiles so the state pytree shape is
            # stable for the fused scan and checkpoints
            self.table.ensure_row_versions()
            log.info("[cluster] pull_cache: %d lines armed",
                     self.pull_cache)
        with obs.setup_span("sampler_build"):
            prob, alias = build_unigram_alias(self.vocab.counts)
            self._alias_prob = jnp.asarray(prob)
            self._alias_idx = jnp.asarray(alias)
        self._resolve_stencil()
        if self.control_settings.enabled:
            self._arm_control()
        log.info("vocab: %d words, %d tokens; table capacity %d",
                 V, self.vocab.total_words, self.table.capacity)
        return self

    def _resolve_stencil(self) -> None:
        """The context side's rendering, from what the model observes at
        build time: a CBOW model in one process, not hogwild, on a
        backend that pushes counted rows renders its contexts by span
        position (``stencil`` reads 1 from here on, visibly) — one
        algorithm, the context sum, rendered by position when the input
        carries positions.  Skip-gram is per-pair by nature, and multi-
        process and hogwild batches are per-pair by construction.
        ``train()`` settles it against the batcher it is handed
        (`_settle_stencil`)."""
        if self.stencil:
            return
        self._stencil_auto = bool(
            not self.sg and self.async_mode != "hogwild"
            and jax.process_count() == 1
            and getattr(self.transfer, "name", "") in ("xla", "hybrid"))
        self.stencil = int(self._stencil_auto)

    def _settle_stencil(self, batcher) -> bool:
        """Whether this ``train()`` call renders spans.  A rendering that
        was asked for stands (and raises where it cannot run); one the
        model resolved itself needs a batcher that renders spans
        (``epoch_stencil``) — ``None`` is the Python batcher train()
        makes for in-memory sentences, which keeps the per-pair stream.
        A step compiled for the other rendering is dropped."""
        if self._stencil_auto:
            want = int(hasattr(batcher, "epoch_stencil"))
            if want != self.stencil:
                self.stencil = want
                self._step, self._fused_cache = None, {}
        return bool(self.stencil)

    def _seed_hot_touched_fraction(self):
        """Expected fraction of the replicated hot head touched by ONE
        coalesced window — the density signal the collective crossover
        prices (key_index.price_hot_collectives): E[unique hot rows] =
        sum over the head of 1-(1-p_i)^draws with p_i the key's FULL-
        vocab probability (the window's draws land on the whole vocab,
        only the head subset is priced), over n_hot.  Same saturation
        model as the window_expected_unique seed
        (hashfrag.expected_unique_rows), restricted to the head.
        ``None`` when there is no hot head — auto then keeps psum."""
        part = getattr(self.table.key_index, "partition", None)
        n_hot = int(getattr(part, "n_hot", 0) or 0)
        if n_hot <= 0:
            return None
        c = np.asarray(self.vocab.counts, np.float64).ravel()
        total = c.sum()
        if total <= 0:
            return None
        head = np.sort(c)[::-1][:n_hot] / total
        draws = self.push_window_size * self.minibatch
        touched = float(np.sum(-np.expm1(
            draws * np.log1p(-np.minimum(head, 1.0)))))
        return min(touched / n_hot, 1.0)

    def _step_split(self):
        """``(mesh, axis, n)`` when the sync step can run SPLIT over the
        table's axis — each of its ``n`` chips rendering, sampling for,
        pulling for and pushing its own share of the batch, `_build_step`'s
        ``run_split`` — else ``None``.  From what the model observes: the
        span rendering with per-center negatives (the one rendering that
        has a split form, `_build_grads_stencil`), on a transfer that
        routes this table's rows to their owners
        (`XlaTransfer.route_mode`), one process, no replicated hot head,
        no numerics plane."""
        tr = self.transfer
        if (not self.stencil or self.sg or self.shared_negatives
                or self._numerics is not None or self.table.n_hot
                or jax.process_count() > 1
                or getattr(tr, "route_mode", None) is None
                or tr.route_mode(self.table.state) != "wrap"):
            return None
        return tr.mesh, tr.axis, tr.shards

    def _splits(self, span, centers: int, n: int) -> bool:
        """Whether a step of this packed ``span`` batch runs split ``n``
        ways: the span's positions divide, a chip's share holds a window,
        and neither push is one the transfer would apply densely (a
        full-table sweep, weighed against the whole batch)."""
        S = (span.shape[-1] - 2 * centers) // 2
        return (S % n == 0 and S // n >= 2 * self.window
                and not any(self.transfer.pushes_dense(
                    slots, self.table.capacity)
                    for slots in (S, centers * (self.negative + 1))))

    # -- the fused step ----------------------------------------------------
    @property
    def _rep(self):
        """The sharding of a step program's carried values: replicated
        over the mesh."""
        return jax.sharding.NamedSharding(self.cluster.mesh, P())

    def _carry(self, x: np.ndarray):
        """A small host value as the steps carry it: committed and
        replicated over the mesh (every process holds the same); data,
        no program."""
        return jax.make_array_from_process_local_data(self._rep, x)

    def _carried_jit(self, donate=("state", "key", "tally"), **more):
        """``jax.jit``'s arguments for a step program (`_carried`): the
        state, the key and the tally donated; the key's data and the
        tally returned replicated over the mesh, as `_carry` places
        them, whatever the compiler would pick — a
        carried value goes back in as it came out, and the step is
        compiled and cached once."""
        rep = self._rep
        return dict(donate_argnames=donate,
                    out_shardings=(None, rep, rep, None), **more)

    def _rekey(self, key_data) -> None:
        """Repoint ``_key`` at the key data a step returned (host only:
        no program runs)."""
        self._key = jax.random.wrap_key_data(key_data, impl=self._key_impl)

    def _settle_key(self) -> None:
        """``_key`` placed as the steps return it (`_carried_jit`): a new
        model's key, or one a caller assigned, sits uncommitted on one
        device, and the step would be entered twice in its dispatch
        cache, once for that key and once for its own.  One read and one
        put; nothing to do from the second ``train()`` on."""
        data = jax.random.key_data(self._key)
        if not (data.committed and data.sharding == self._rep):
            self._rekey(self._carry(np.asarray(data)))

    def _build_step(self):
        """Sync step: grads against current state + immediate push.  The
        table state is donated — the update is in-place in HBM.

        The program owns its bookkeeping (`_carried`): it takes the
        model's key and the call's tally (both donated; ``tally=None``
        starts one) and returns ``(state, next key's data, tally, es)``,
        so ``train()`` launches nothing else for a step."""
        split = self._step_split()
        grads_fn = self._build_grads()
        split_grads_fn = self._build_grads(split) if split else None
        apply_fn = self._build_apply()
        # numerics plane: `num is None` (the default) leaves the traced
        # program untouched — the branches below are Python-time
        from swiftmpi_tpu.obs import numerics as obs_numerics
        num = self._numerics
        n_hot = self.table.n_hot
        gfields = tuple(self.access.grad_fields)
        # row-write counters (telemetry on only: a step built without
        # them returns no count, so the timed program carries none): the
        # step's sparse pushes' distinct valid rows x fields, the 8-row
        # tiles the tile kernel moved for them x fields and the copies
        # that moved those one way x fields, three more results, fetched
        # with the loss — and, where the transfer routes
        # rows to their owners, the rows it routed and the bucket slots
        # it exchanged for them, two more again
        telemetry = obs.get_registry().enabled
        count_rows = getattr(self.transfer, "count_rows_written", None) \
            if telemetry else None
        count_routed = getattr(self.transfer, "count_routed", None) \
            if telemetry else None

        def counted(fn, *args):
            """``(fn(*args), counts)`` with the transfer's tapes held."""
            with contextlib.ExitStack() as tapes:
                rows = count_rows and tapes.enter_context(count_rows())
                routed = count_routed and tapes.enter_context(count_routed())
                out = fn(*args)
            counts = () if rows is None else tuple(
                sum(rows, jnp.zeros((3,), jnp.int32)))
            if routed:
                counts += tuple(sum(c, jnp.int32(0)) for c in zip(*routed))
            return out, counts

        def run(state, statics, batch, key, grads_fn=grads_fn, **shape):
            def grads_and_apply():
                pushes, es, ec = grads_fn(state, *statics, *batch, key,
                                          **shape)
                return apply_fn(state, pushes), pushes, es, ec
            (out, pushes, es, ec), counts = counted(grads_and_apply)
            if num is not None:
                obs_numerics.stage_step(
                    num, state, out,
                    obs_numerics.spec_stats(pushes, n_hot),
                    es, ec, gfields)
            return (out, es, ec, *counts)

        def run_split(state, statics, batch, key, **shape):
            """`run` with every chip of the table's axis rendering,
            sampling for, pulling for and pushing its own share of the
            batch (`_build_grads_stencil`), the table's shard its state:
            what leaves a chip is what `transfer/route.py` exchanges and
            the sums of the loss and the counters."""
            mesh, axis, _ = split

            def body(state, statics, batch, key):
                out, *sums = run(state, statics, batch, key,
                                 grads_fn=split_grads_fn, **shape)
                return (out, *(jax.lax.psum(x, axis) for x in sums))
            rows = dict.fromkeys(state, P(axis))
            # the loss's two sums, then the counters `counted` returns
            n_sums = 2 + 3 * bool(count_rows) + 2 * bool(count_routed)
            return jax.shard_map(
                body, mesh=mesh, in_specs=(rows, P(), P(), P()),
                out_specs=(rows, *[P()] * n_sums),
                check_vma=False)(state, statics, batch, key)

        if self.stencil:
            # the batch is one packed buffer (StencilBatch.pack), cut
            # into its four fields inside the program: one put a step
            @partial(jax.jit, **self._carried_jit(static_argnames="centers"))
            def step(state, slot_of_vocab, alias_prob, alias_idx,
                     span, key, tally=None, *, centers):
                fn = run_split if split and self._splits(
                    span, centers, split[2]) else run
                return _carried(key, tally, lambda sub: fn(
                    state, (slot_of_vocab, alias_prob, alias_idx),
                    (span,), sub, centers=centers))

            return obs.costs.track("w2v_step", step)

        @partial(jax.jit, **self._carried_jit())
        def step(state, slot_of_vocab, alias_prob, alias_idx,
                 centers, contexts, ctx_mask, key, tally=None):
            return _carried(key, tally, lambda sub: run(
                state, (slot_of_vocab, alias_prob, alias_idx),
                (centers, contexts, ctx_mask), sub))

        return obs.costs.track("w2v_step", step)

    def _make_step(self, hogwild: bool = False):
        """The step program(s) ``train()`` drives in this model's mode,
        all in the step programs' form (`_carried`): the hogwild group
        step, the sync step (``local_steps <= 1``), or the async
        ``(grads, apply)`` pair.  The one place a mode picks its
        programs — ``train()`` and the control plane's safe-point
        recompile (`_rebuild_step`) both come here.  The building is the
        set-up span ``step_build``; a program's first call after it is
        its ``first_step`` (``obs.costs.TrackedFn``)."""
        with obs.setup_span("step_build"):
            if hogwild:
                return self._build_hogwild_step(max(self.local_steps, 1))
            if self.local_steps <= 1:
                return self._build_step()
            return (obs.costs.track("w2v_grads", self._build_async_grads()),
                    obs.costs.track("w2v_apply",
                                    jax.jit(self._build_apply())))

    def _fused_for(self, n_inner: int):
        """Compiled fused scan of ``n_inner`` steps, cached per length.
        The epoch loop fuses FULL groups of ``inner_steps`` and (since
        round 4) the tail group too — a small corpus whose epoch is a
        handful of batches otherwise degrades to per-batch dispatches,
        each paying the per-dispatch overhead (not measured on this
        stack).

        Distinct tail lengths are bounded by [2, inner_steps), but NOT
        fixed per corpus: per-epoch subsampling re-randomization (e.g.
        native.py's seed+epoch_i) shifts the full-batch count between
        epochs, so a multi-epoch run may compile a few tail lengths as
        it encounters them — amortized across the run and persisted by
        the JAX compilation cache."""
        fn = self._fused_cache.get(n_inner)
        if fn is None:
            # cost-catalog funnel (ISSUE 14): one name covers every
            # fused length — each length is its own handle, so a new
            # tail length books a compile, never a retrace
            with obs.setup_span("step_build"):
                fn = self._fused_cache[n_inner] = obs.costs.track(
                    "w2v_multi", self._build_multi_step(n_inner),
                    steps_per_call=n_inner)
        return fn

    def _build_multi_step(self, n_inner: int):
        """``n_inner`` training steps in one dispatch via lax.scan —
        amortizes per-call dispatch latency (the single-chip bottleneck:
        one fused step executes in ~0.1ms, comparable to dispatch).
        Batches arrive stacked on a leading (n_inner, ...) axis."""
        grads_fn = self._build_grads()
        if self.push_window_size > 1:
            return self._build_multi_step_windowed(n_inner, grads_fn)
        apply_fn = self._build_apply()
        # numerics plane: armed, each scan step folds its push stats
        # into extra scan outputs and ONE bundle ships per dispatch;
        # off (num None), the traced program is exactly the legacy one
        from swiftmpi_tpu.obs import numerics as obs_numerics
        num = self._numerics
        n_hot = self.table.n_hot
        gfields = tuple(self.access.grad_fields)

        def run(state, statics, batches, key, **shape):
            keys = jax.random.split(key, n_inner)
            state0 = state

            def body(state, xs):
                *batch, k = xs
                pushes, es, ec = grads_fn(state, *statics, *batch, k,
                                          **shape)
                if num is None:
                    return apply_fn(state, pushes), (es, ec)
                return apply_fn(state, pushes), (
                    es, ec, obs_numerics.spec_stats(pushes, n_hot))

            state, outs = jax.lax.scan(body, state, (*batches, keys))
            if num is None:
                es, ec = outs
            else:
                es, ec, stats = outs
                obs_numerics.stage_step(
                    num, state0, state, tuple(s.sum() for s in stats),
                    es.sum(), ec.sum(), gfields)
            return state, es.sum(), ec.sum()

        if self.stencil:
            @partial(jax.jit, **self._carried_jit(static_argnames="centers"))
            def multi_st(state, slot_of_vocab, alias_prob, alias_idx,
                         spans_s, key, tally=None, *, centers):
                return _carried(key, tally, lambda sub: run(
                    state, (slot_of_vocab, alias_prob, alias_idx),
                    (spans_s,), sub, centers=centers))

            return multi_st

        @partial(jax.jit, **self._carried_jit())
        def multi(state, slot_of_vocab, alias_prob, alias_idx,
                  centers_s, contexts_s, masks_s, key, tally=None):
            return _carried(key, tally, lambda sub: run(
                state, (slot_of_vocab, alias_prob, alias_idx),
                (centers_s, contexts_s, masks_s), sub))

        return multi

    def _build_multi_step_windowed(self, n_inner: int, grads_fn):
        """Window-coalesced fused scan ([cluster] push_window = W > 1):
        steps inside a window compute gradients against the FROZEN
        window-start state (scan carries it unchanged) and stack their
        PushSpecs as scan outputs; the window then applies each push
        family with ONE ``transfer.push_window`` exchange.  A Python loop
        walks the ceil(n_inner / W) windows inside the same jit, so the
        dispatch count per fused group is unchanged while collective
        dispatches drop ~W-fold.  Staleness is bounded by W-1 steps (see
        docs/ARCHITECTURE.md "Window-coalesced push")."""
        W = self.push_window_size
        apply_window = self._build_apply_window()
        bounds = [(s, min(s + W, n_inner)) for s in range(0, n_inner, W)]
        mesh = getattr(self.cluster, "mesh", None)
        replicated = (jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()) if mesh is not None else None)
        # numerics plane: the stacked (W, ...) push buffers already
        # exist per window, so armed stats fold over them with no extra
        # scan outputs; off (num None) traces the legacy program
        from swiftmpi_tpu.obs import numerics as obs_numerics
        num = self._numerics
        n_hot = self.table.n_hot
        gfields = tuple(self.access.grad_fields)

        def run_windows(state, statics, keys, xs_all, **shape):
            es_tot, ec_tot = jnp.float32(0), jnp.float32(0)
            state0 = state
            if num is not None:
                gacc = (jnp.float32(0), jnp.float32(0), jnp.int32(0))
            for s, e in bounds:
                xs = tuple(x[s:e] for x in xs_all) + (keys[s:e],)

                def body(carry, x):
                    # carry is the window-start state, returned untouched:
                    # every step in the window sees the same snapshot
                    pushes, es, ec = grads_fn(carry, *statics, *x, **shape)
                    return carry, (pushes, es, ec)

                _, (pushes_s, es, ec) = jax.lax.scan(body, state, xs)
                if replicated is not None:
                    # the stacked (W, ...) push buffers must stay
                    # replicated: letting GSPMD infer a sharding for them
                    # from the row-sharded scatter consumer miscompiles
                    # the partitioned scatter (wrong sums on the emulated
                    # mesh) — pin them before the window apply
                    pushes_s = jax.tree_util.tree_map(
                        lambda x: jax.lax.with_sharding_constraint(
                            x, replicated), pushes_s)
                if num is not None:
                    w = obs_numerics.spec_stats(pushes_s, n_hot)
                    gacc = tuple(a + b for a, b in zip(gacc, w))
                state = apply_window(state, pushes_s)
                es_tot += es.sum()
                ec_tot += ec.sum()
            if num is not None:
                obs_numerics.stage_step(num, state0, state, gacc,
                                        es_tot, ec_tot, gfields)
            return state, es_tot, ec_tot

        if self.stencil:
            @partial(jax.jit, **self._carried_jit(static_argnames="centers"))
            def multi_st(state, slot_of_vocab, alias_prob, alias_idx,
                         spans_s, key, tally=None, *, centers):
                return _carried(key, tally, lambda sub: run_windows(
                    state, (slot_of_vocab, alias_prob, alias_idx),
                    jax.random.split(sub, n_inner), (spans_s,),
                    centers=centers))

            return multi_st

        @partial(jax.jit, **self._carried_jit())
        def multi(state, slot_of_vocab, alias_prob, alias_idx,
                  centers_s, contexts_s, masks_s, key, tally=None):
            return _carried(key, tally, lambda sub: run_windows(
                state, (slot_of_vocab, alias_prob, alias_idx),
                jax.random.split(sub, n_inner),
                (centers_s, contexts_s, masks_s)))

        return multi

    def _build_apply_window(self):
        """Window analogue of :meth:`_build_apply`: each stacked (W, ...)
        PushSpec family goes through ONE ``transfer.push_window`` call."""
        access = self.access
        transfer = self.transfer

        def apply_window(state, pushes):
            for spec in pushes:
                state = transfer.push_window(
                    state, spec.slots, spec.grads, access,
                    mean=spec.mean,
                    counts=getattr(spec, "counts", None))
            return state

        return apply_window

    def _build_hogwild_step(self, n_inner: int):
        """Genuinely unsynchronized async SGD — the TPU rendering of the
        reference's async/global variant (word2vec_global.h:577-651),
        where worker threads pull/push against the server with NO barrier
        and gradients are arbitrarily stale.

        SPMD can't express literal thread races, but it can express their
        semantics: every device becomes an independent worker with a FULL
        replica of the table (the reference's LocalParamCache, taken to
        its limit), trains ``n_inner`` batches on its own stream — own
        negatives, own AdaGrad accumulation, zero cross-device traffic —
        then every worker's RAW GRADIENT pushes are all_gathered and
        applied to the shared base SEQUENTIALLY through the access
        method, exactly as the reference server applies each thread's
        push in arrival order against the live accumulators
        (server.h:159-176; worker-major order here is one valid
        linearization of the nondeterministic arrival order).

        Why not psum the replicas' deltas (this mode's first rendering):
        each delta composes that worker's AdaGrad trajectory from the
        SAME base accumulator, so summing them applies every worker's
        full-size early steps to shared hot rows — an effective
        n_workers-times overstep on frequent words that measurably
        diverges (parity soak: hogwild loss rising by epoch 3, +72% vs
        sync).  Sequential re-application lets each push see the accum
        state the previous pushes grew, like the reference.  Staleness
        bound = ``n_inner`` batches x ``n_devices`` workers (the
        reference's is unbounded only by thread scheduling).

        Trades the row-sharded layout for replication during the async
        phase (a vocab-scale table fits one device by orders of
        magnitude); the ``data``/``model`` sharded layout is the sync
        path's concern.  Memory note: reconciliation rings the STATE
        through the workers (each applies its own, locally-held pushes
        to the passing chain), so peak extra memory is one table-state
        copy (O(capacity x d), ~27MB at demo.conf scale) on top of the
        worker's own push sequence (O(local_steps x push_rows x d),
        which the gradient scan holds anyway) — no n_workers-scaled
        materialization.  Time note: the apply is inherently SEQUENTIAL
        over all ``n_workers x local_steps`` pushes (that is its
        semantics — each AdaGrad apply must see the accumulators the
        previous pushes grew), and every device runs the full chain
        redundantly (each computing a different rotation, only the
        worker-major one kept); reconciliation wall-time therefore grows
        linearly with worker count, so large fleets amortize it with
        bigger ``local_steps`` or prefer the snapshot
        (``local_steps``-only) async mode."""
        if getattr(self.transfer, "name", "") in ("tpu", "hybrid"):
            raise ValueError(
                "async_mode=hogwild requires the gather/scatter 'xla' "
                "transfer: each worker replica trains locally, and the "
                "'tpu'/'hybrid' backends' shard_map routing cannot nest "
                "inside the per-worker mesh (set [cluster] transfer: xla)")
        # Single-process SPMD mode: the worker axis spans this process's
        # devices.  Multi-process runs are routed by train() to the
        # snapshot bounded-staleness mode (measured loss envelope within
        # +0.02% of hogwild at realistic scale — docs/ARCHITECTURE.md
        # "Async modes") rather than refused.
        grads_fn = self._build_grads()
        apply_fn = self._build_apply()
        mesh = self.cluster.mesh
        workers = mesh.devices.reshape(-1)
        wmesh = jax.sharding.Mesh(workers, ("worker",))
        n_workers = len(workers)

        from jax.sharding import PartitionSpec as P

        @partial(jax.shard_map, mesh=wmesh,
                 in_specs=(P(), P(), P(), P(),
                           P("worker"), P("worker"), P("worker"), P()),
                 out_specs=(P(), P(), P()), check_vma=False)
        def _workers(state, slot_of_vocab, alias_prob, alias_idx,
                     centers_s, contexts_s, masks_s, key):
            wid = jax.lax.axis_index("worker")
            keys = jax.random.split(jax.random.fold_in(key, wid), n_inner)
            # local batch-stack view is already (n_inner, B, ...): the
            # global (n_workers * n_inner, ...) leading axis is sharded
            centers_l, contexts_l, masks_l = centers_s, contexts_s, masks_s

            def body(local, xs):
                c, x, m, k = xs
                pushes, es, ec = grads_fn(
                    local, slot_of_vocab, alias_prob, alias_idx, c, x, m, k)
                # the local replica evolves with this worker's own pushes
                # (its stale view); the same pushes are also carried out
                # for the shared sequential apply
                return apply_fn(local, pushes), (pushes, es, ec)

            _, (pushes_l, es, ec) = jax.lax.scan(
                body, state, (centers_l, contexts_l, masks_l, keys))
            # reconcile: every worker's push sequence, applied to the
            # shared base one push at a time (worker-major) so each
            # AdaGrad application sees the accumulators the previous
            # pushes grew — the reference server's arrival-order apply.
            # RING THE STATE, NOT THE PUSHES (round-2 all_gathered every
            # sequence to every device: 2.2GB at 16K-batch/8-worker/
            # 2-step): each device applies its OWN pushes to the chain
            # state passing through, so push data never crosses the
            # ring and per-round traffic is one table state (~27MB at
            # demo.conf scale).  After round 0 (own apply) + n-1
            # shift+apply rounds, the device with the highest id holds
            # exactly A_{n-1}(...A_1(A_0(base))) — the worker-major
            # linearization — and one masked psum broadcasts it.
            shift = [(i, (i + 1) % n_workers)
                     for i in range(n_workers)]

            def apply_own(st):
                def apply_step(st, s_pushes):
                    return apply_fn(st, s_pushes), None
                st, _ = jax.lax.scan(apply_step, st, pushes_l)
                return st

            chain = apply_own(state)
            for _ in range(n_workers - 1):
                chain = jax.tree_util.tree_map(
                    lambda x: jax.lax.ppermute(x, "worker", shift),
                    chain)
                chain = apply_own(chain)
            is_last = wid == n_workers - 1
            new_state = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(
                    jnp.where(is_last, x, jnp.zeros_like(x)), "worker"),
                chain)
            return new_state, jax.lax.psum(es.sum(), "worker"), \
                jax.lax.psum(ec.sum(), "worker")

        @partial(jax.jit, **self._carried_jit())
        def step(state, slot_of_vocab, alias_prob, alias_idx,
                 centers_s, contexts_s, masks_s, key, tally=None):
            return _carried(key, tally, lambda sub: _workers(
                state, slot_of_vocab, alias_prob, alias_idx,
                centers_s, contexts_s, masks_s, sub))

        return obs.costs.track("w2v_hogwild", step,
                               steps_per_call=n_inner), n_workers

    def _build_grads(self, split=None):
        """Gradient phase of the step: pull rows, CBOW- or skip-gram-NS
        math, per-key mean normalization — no push.  Split out so the async
        (``local_steps``) mode can compute grads against a *stale* state
        snapshot while pushes land on the live state.  ``split``:
        `_step_split`'s answer, for the one rendering it is given for."""
        if self.stencil:
            if self.sg:
                raise ValueError(
                    "stencil is a CBOW-only rendering (span positions "
                    "index a center's context window); drop sg or "
                    "stencil")
            if getattr(self.transfer, "name", "") not in ("xla", "hybrid"):
                raise ValueError(
                    "the stencil rendering pushes its span family "
                    "through push_span (XlaTransfer, or HybridTransfer's "
                    "split hot/tail span paths) — set [cluster] "
                    "transfer: xla or hybrid")
            if self.shared_negatives:
                self.resolved_rendering = "stencil_shared"
                return self._build_grads_stencil(shared=True)
            self.resolved_rendering = "stencil"
            return self._build_grads_stencil(shared=False, split=split)
        if self.sg:
            if self.shared_negatives:
                self.resolved_rendering = "sg_shared"
                return self._build_grads_sg_shared()
            self.resolved_rendering = "sg"
            return self._build_grads_sg()
        if self.shared_negatives:
            self.resolved_rendering = "shared"
            return self._build_grads_shared()
        self.resolved_rendering = "gather"
        access = self.access
        transfer = self.transfer
        K = self.negative
        alpha = self.alpha
        d = self.row_width

        def grads_fn(state, slot_of_vocab, alias_prob, alias_idx,
                     centers, contexts, ctx_mask, key):
            B, W2 = contexts.shape
            t_slots, ctx_slots, t_valid = _cbow_targets(
                slot_of_vocab, alias_prob, alias_idx, centers, contexts,
                ctx_mask, key, K)
            with obs.named_scope("sample"):
                t_slots = jnp.where(t_valid, t_slots, -1)

            with obs.named_scope("math"):
                # split pulls: targets need only h, contexts only v —
                # pulling both fields for the union of slots would gather
                # twice the bytes and discard half (fp32 upcast restores
                # precision when the table stores bf16)
                h_t = transfer.pull(
                    state, t_slots.reshape(-1), access, fields=("h",)
                )["h"].reshape(B, K + 1, d).astype(jnp.float32)
                v_ctx = transfer.pull(
                    state, ctx_slots.reshape(-1), access, fields=("v",)
                )["v"].reshape(B, W2, d).astype(jnp.float32)

                neu1 = jnp.sum(v_ctx * ctx_mask[..., None], axis=1)   # (B, d)
                f = jnp.einsum("bd,bkd->bk", neu1, h_t)
                labels = jnp.concatenate(
                    [jnp.ones((B, 1)), jnp.zeros((B, K))], axis=1)
                g = (labels - sigmoid_clipped(f)) * alpha
                g = jnp.where(t_valid, g, 0.0)  # (B, K+1)

                h_contrib = g[..., None] * neu1[:, None, :]  # (B,K+1,d)
                neu1e = jnp.einsum("bk,bkd->bd", g, h_t)              # (B, d)
                v_contrib = jnp.where(ctx_mask[..., None],
                                      neu1e[:, None, :], 0.0)  # (B,2W,d)

                pushes = _assemble_push(
                    t_slots.reshape(-1), ctx_slots.reshape(-1),
                    h_contrib.reshape(-1, d), v_contrib.reshape(-1, d))

                err_sum = jnp.sum(1e4 * g * g)          # word2vec.h:593
                err_cnt = t_valid.sum()
            return pushes, err_sum, err_cnt

        return grads_fn

    def _build_grads_shared(self):
        """CBOW-NS with batch-shared negatives — the TPU-first rendering
        of negative sampling (opt-in, ``shared_negatives: 1``).

        The reference draws K negatives per center (word2vec.h:577-586),
        which on TPU costs a B*(K+1)-row random gather — the measured
        bottleneck (row gathers run ~5% of HBM peak; see
        docs/ARCHITECTURE.md).  Sharing one K-negative set across the
        batch — standard practice in modern embedding trainers, same
        expected gradient for the negative term up to sampling variance —
        restructures the math MXU-first:

          h gather:   B + K rows instead of B*(K+1)   (~20x less)
          f_neg:      neu1 @ h_neg^T    — a (B,d)x(d,K) matmul
          gh_neg:     g_neg^T @ neu1    — a (K,B)x(B,d) matmul, DENSE
                      per-negative grads (no scatter at all for negs)
          neu1e:      g_pos*h_pos + g_neg @ h_neg — matmul again

        Per-key mean normalization and the (negative == center) skip are
        preserved; the error metric is the same accu(1e4 g^2).  NOT
        loss-parity with the reference's RNG stream (different negative
        correlation structure) — the parity mode stays the default and
        the oracle tests pin it."""
        access = self.access
        transfer = self.transfer
        K = self.shared_pool
        alpha = self.alpha
        d = self.row_width

        def grads_fn(state, slot_of_vocab, alias_prob, alias_idx,
                     centers, contexts, ctx_mask, key):
            B, W2 = contexts.shape
            with obs.named_scope("sample"):
                negs = sample_alias(key, alias_prob, alias_idx, (K,))
                c_slots = slot_of_vocab[centers]                  # (B,)
                n_slots = slot_of_vocab[negs]                     # (K,)
                ctx_slots = jnp.where(ctx_mask, slot_of_vocab[contexts], -1)
                row_valid = ctx_mask.any(axis=1)

            with obs.named_scope("math"):
                pulled_h = transfer.pull(
                    state, jnp.concatenate([c_slots, n_slots]), access,
                    fields=("h",))["h"].astype(jnp.float32)
                h_pos = pulled_h[:B]                              # (B, d)
                h_neg = pulled_h[B:B + K]                         # (K, d)
                v_ctx = transfer.pull(
                    state, ctx_slots.reshape(-1), access, fields=("v",)
                )["v"].reshape(B, W2, d).astype(jnp.float32)

                neu1 = jnp.sum(v_ctx * ctx_mask[..., None], axis=1)
                f_pos = jnp.einsum("bd,bd->b", neu1, h_pos)       # (B,)
                f_neg = neu1 @ h_neg.T                            # (B, K) MXU
                g_pos = (1.0 - sigmoid_clipped(f_pos)) * alpha
                g_pos = jnp.where(row_valid, g_pos, 0.0)
                # negative == center skipped (word2vec.h:584-586)
                n_valid = (negs[None, :] != centers[:, None]) \
                    & row_valid[:, None]
                g_neg = jnp.where(n_valid,
                                  (0.0 - sigmoid_clipped(f_neg)) * alpha, 0.0)
                # keep the objective's positive/negative balance at the
                # configured `negative` draws per center: the pool evaluates
                # K pairs per center, so each carries weight negative/K
                gw = g_neg * (self.negative / K)

                gh_pos = g_pos[:, None] * neu1                    # (B, d)
                gh_neg = gw.T @ neu1                              # (K, d) MXU
                neu1e = g_pos[:, None] * h_pos + gw @ h_neg       # (B, d) MXU
                v_contrib = jnp.where(ctx_mask[..., None],
                                      neu1e[:, None, :], 0.0)

                # Three push families.  Positives and contexts keep the
                # reference's per-key mean normalization.  The pool rows are
                # pushed as their OWN family with SUM semantics: each row
                # already carries the sum of its ~B per-pair contributions —
                # the exact gradient of the pairwise NS objective — and it
                # must NOT share a count vector with the centers, or a
                # frequent word appearing hundreds of times as a center in
                # the same batch would have its one summed negative row
                # divided by that count (~100-1000x attenuation at bench
                # shapes: exactly the 'negatives stop training' collapse
                # documented above, smuggled back in through normalization).
                # Duplicate pool draws of one key sum too — each draw is a
                # sample, as in the reference's per-center draws.
                pos_slots = jnp.where(row_valid, c_slots, -1)
                neg_slots = jnp.where(n_valid.any(axis=0), n_slots, -1)
                cslots_flat = ctx_slots.reshape(-1)
                v_flat = v_contrib.reshape(-1, d)
                pushes = (PushSpec(pos_slots, {"h": gh_pos}, mean=True),
                          PushSpec(neg_slots, {"h": gh_neg}),
                          PushSpec(cslots_flat, {"v": v_flat}, mean=True))

                # loss terms carry the same negative/K weighting as the
                # gradients (advisor r04, both shared-pool variants): a
                # center contributes ~1 positive + ~`negative` weighted pool
                # terms, keeping the reported loss scale-comparable with the
                # per-center parity CBOW rendering
                ratio = self.negative / K
                err_sum = jnp.sum(1e4 * g_pos * g_pos) \
                    + ratio * jnp.sum(1e4 * g_neg * g_neg)
                err_cnt = row_valid.sum() + ratio * n_valid.sum()
            return pushes, err_sum, err_cnt

        return grads_fn

    def _build_grads_stencil(self, shared: bool, split=None):
        """Positional-stencil rendering of the CBOW gradient phase: the
        context side is computed over the batch's stream SPAN, position
        by position, never over a ``(B, 2W)`` pair grid.

        Consecutive centers of a sentence share their windows, so every
        stream position stands in up to 2W rows of the per-pair grid,
        and 43 % of that grid is the dynamic window's padding
        (``pair_fill_share``).  The batcher emits the span instead
        (data/text.py ``StencilBatch``: ``S`` tokens and sentence ids,
        ``B`` center positions and half windows, one packed buffer; the
        native loader emits the identical wire format) and the step:

          v_span   = pull the S span rows       — ONE pull, S rows
          neu1_pos = Σ_{o=±1..W} v_span[p+o] · [|o| ≤ half_pos[p]]
                     · [same sentence]           — 2W statically shifted
                     slices of the (S, d) array: dense, no gather
          neu1     = neu1_pos[center_pos]        — B rows of a small array
          ... the target side (``h`` rows of the center and its
              negatives), as the per-pair rendering has it ...
          neu1e at its center's position, then
          vg[p] = Σ_o neu1e_pos[p-o] · mask, vc[p] = Σ_o mask
                     — the same 2W shifts, transposed
          push S counted rows (``mean=True``, ``vc`` the multiplicity;
          a position no window covers goes as slot -1)

        ``half_pos`` is ``half`` placed at the centers' positions and 0
        elsewhere, so a position that is no center has an empty window.
        Sentence boundaries and the reference's dynamic window shrink
        (word2vec.h:556) are masks, equal by construction to the
        per-pair batcher's expansion — data/text.py ``stencil_to_cbow``
        is the executable statement of that equivalence and the parity
        tests pin it.  The mathematics is the per-pair rendering's, f32
        throughout, every valid pair summed: only the order of summation
        differs.

        ``shared=False``: per-center K negatives drawn from the SAME
        sampling stream as the parity gather rendering — directly
        checkable against the numpy oracle.  ``shared=True``
        (``shared_negatives: 1``): the batch-shared pool of
        ``_build_grads_shared`` on the h side.

        ``split`` (`_step_split`: ``(mesh, axis, n)``): the rendering of
        ONE chip's share, to be run under a ``shard_map`` over the table's
        ``axis``.  The span is cut by position: chip ``i`` owns positions
        ``[i S/n, (i+1) S/n)``, the centers standing there (a run of the
        batch's ascending ``center_pos``), and ``W`` positions of halo on
        either side, which it pulls itself.  The target side is rendered
        by POSITION, not by center (a position that is no center is an
        invalid row), so a center's window sum is the row at its own
        position and nothing is gathered or scattered between the two
        indexings.  A position in a halo takes gradient from the centers
        of two chips; each pushes its own part with its own count and the
        push's ``mean`` divides the owner's sum by the summed counts, as
        it does for any key pushed twice.  The negatives are the global
        ``(B, K)`` draw's rows (`_negative_slots` ``take``): the stream
        is the unsplit step's, letter for letter."""
        access = self.access
        transfer = self.transfer
        W = self.window
        alpha = self.alpha
        d = self.row_width
        K = self.shared_pool if shared else self.negative
        offsets = [o for o in range(-W, W + 1) if o]

        def shifted(x, o):
            """``x[p + o]`` at every span position ``p``, of an ``x``
            padded by ``W`` on both ends of its first axis."""
            return jax.lax.slice_in_dim(x, W + o, W + o + x.shape[0] - 2 * W)

        def pad(x, fill):
            return jnp.pad(x, [(W, W)] + [(0, 0)] * (x.ndim - 1),
                           constant_values=fill)

        def span_parts(state, slot_of_vocab, span, centers):
            tokens, sent_id, center_pos, half = unpack_span(span, centers)
            S = tokens.shape[0]
            with obs.named_scope("sample"):
                span_slots = jnp.where(sent_id >= 0, slot_of_vocab[tokens],
                                       -1)
                row_valid = center_pos >= 0
                cp = jnp.clip(center_pos, 0, S - 1)
                c_words = tokens[cp]                             # (B,) vocab
                c_slots = jnp.where(row_valid, span_slots[cp], -1)
                # centers stand at ascending, distinct positions; padded
                # ones (the batch's tail) go past the end and drop
                at = jnp.where(row_valid, center_pos, S)
                half_pos = jnp.zeros((S,), jnp.int32).at[at].set(
                    half, mode="drop", unique_indices=True,
                    indices_are_sorted=True)
                fwd, bwd = windows(sent_id, half_pos)
            with obs.named_scope("math"):
                # THE pull this rendering exists for: S rows, once
                v_pad = pad(transfer.pull(
                    state, span_slots, access, fields=("v",)
                )["v"].astype(jnp.float32), 0.0)                 # (S+2W, d)
                neu1_pos = sum(jnp.where(m[:, None], shifted(v_pad, o), 0.0)
                               for o, m in zip(offsets, fwd))
                neu1 = neu1_pos[cp]                              # (B, d)
            return span_slots, c_words, c_slots, row_valid, at, bwd, neu1

        def windows(sent_id, half_pos):
            """fwd[o][p]: position p + o is in center p's window;
            bwd[o][p]: p is in the window of the center at p - o."""
            half_pad = pad(half_pos, 0)
            # a padding id of its own: no window reaches past an end
            sid_pad = pad(sent_id, -2)
            fwd = [(abs(o) <= half_pos)
                   & (shifted(sid_pad, o) == sent_id) for o in offsets]
            bwd = [(abs(o) <= shifted(half_pad, -o))
                   & (shifted(sid_pad, -o) == sent_id) for o in offsets]
            return fwd, bwd

        def span_parts_split(state, slot_of_vocab, span, centers):
            """`span_parts` of this chip's share: its ``S / n`` positions
            and ``W`` of halo either side, the centers as rows of its own
            positions."""
            _, axis, n = split
            _, _, center_pos, half = unpack_span(span, centers)
            B = centers
            S = (span.shape[0] - 2 * B) // 2
            own = S // n
            lo = jax.lax.axis_index(axis) * own
            core = slice(W, W + own)
            with obs.named_scope("sample"):
                # tokens and sentence ids of the chip's positions, read
                # where `unpack_span` has them in the packed batch
                pos = lo - W + jnp.arange(own + 2 * W, dtype=jnp.int32)
                inside = (pos >= 0) & (pos < S)
                pos = jnp.clip(pos, 0, S - 1)
                tokens = span[pos]
                sent_id = jnp.where(inside, span[S + pos], -2)
                span_slots = jnp.where(sent_id >= 0, slot_of_vocab[tokens],
                                       -1)
                # the centers standing here: a run of the batch's
                # ascending positions, from the first at or past `lo`
                b = jnp.sum((center_pos >= 0) & (center_pos < lo),
                            dtype=jnp.int32) + jnp.arange(own,
                                                          dtype=jnp.int32)
                rank = jnp.clip(b, 0, B - 1)
                cp = center_pos[rank]
                at = jnp.where((b < B) & (cp >= lo) & (cp < lo + own),
                               cp - lo, own)
                placed = dict(mode="drop", unique_indices=True,
                              indices_are_sorted=True)
                half_pos = pad(jnp.zeros((own,), jnp.int32).at[at].set(
                    half[rank], **placed), 0)
                # the center standing at each position, -1: none
                rank = jnp.full((own,), -1, jnp.int32).at[at].set(
                    rank, **placed)
                row_valid = rank >= 0
                c_words = tokens[core]
                c_slots = jnp.where(row_valid, span_slots[core], -1)
                fwd, bwd = windows(sent_id, half_pos)
            with obs.named_scope("math"):
                v_pad = pad(transfer.pull(
                    state, span_slots, access, fields=("v",)
                )["v"].astype(jnp.float32), 0.0)
                neu1 = sum(jnp.where(m[:, None], shifted(v_pad, o), 0.0)
                           for o, m in zip(offsets, fwd))[core]
            return (span_slots, c_words, c_slots, row_valid,
                    jnp.clip(rank, 0, B - 1), bwd, neu1)

        def v_push(span_slots, bwd, at_positions, neu1e):
            with obs.named_scope("math"):
                # invert the stencil by the transposed shifts: a position
                # takes the gradient of every center whose window holds
                # it, and their number rides along so the push's mean
                # divides by the true pair count
                e_pad = pad(at_positions(neu1e), 0.0)
                vg = sum(jnp.where(m[:, None], shifted(e_pad, -o), 0.0)
                         for o, m in zip(offsets, bwd))
                vc = sum(m.astype(jnp.float32) for m in bwd)
                # a position no window covers pushes no row
                return PushSpec(jnp.where(vc > 0, span_slots, -1),
                                {"v": vg}, mean=True, counts=vc)

        def grads_fn(state, slot_of_vocab, alias_prob, alias_idx,
                     span, key, *, centers):
            B = centers
            if split:
                # a row a position of the chip's own: the centers stand
                # where they stand, and draw `take`'s rows of the batch's
                (span_slots, c_words, c_slots, row_valid, take, bwd,
                 neu1) = span_parts_split(state, slot_of_vocab, span,
                                          centers)
                rows = neu1.shape[0]

                def at_positions(neu1e):
                    return pad(neu1e, 0.0)
            else:
                (span_slots, c_words, c_slots, row_valid, at, bwd,
                 neu1) = span_parts(state, slot_of_vocab, span, centers)
                rows, take = B, None

                def at_positions(neu1e):
                    return jnp.zeros((span_slots.shape[0], d),
                                     jnp.float32).at[at].set(
                        neu1e, mode="drop", unique_indices=True,
                        indices_are_sorted=True)
            if shared:
                with obs.named_scope("sample"):
                    negs = sample_alias(key, alias_prob, alias_idx, (K,))
                    n_slots = slot_of_vocab[negs]                # (K,)
                with obs.named_scope("math"):
                    pulled_h = transfer.pull(
                        state, jnp.concatenate([c_slots, n_slots]), access,
                        fields=("h",))["h"].astype(jnp.float32)
                    h_pos = pulled_h[:B]
                    h_neg = pulled_h[B:B + K]
                    f_pos = jnp.einsum("bd,bd->b", neu1, h_pos)
                    f_neg = neu1 @ h_neg.T                       # (B, K) MXU
                    g_pos = jnp.where(
                        row_valid, (1.0 - sigmoid_clipped(f_pos)) * alpha,
                        0.0)
                    # negative == center skipped (word2vec.h:584-586)
                    n_valid = (negs[None, :] != c_words[:, None]) \
                        & row_valid[:, None]
                    g_neg = jnp.where(
                        n_valid, (0.0 - sigmoid_clipped(f_neg)) * alpha, 0.0)
                    gw = g_neg * (self.negative / K)
                    gh_pos = g_pos[:, None] * neu1
                    gh_neg = gw.T @ neu1                         # (K, d) MXU
                    neu1e = g_pos[:, None] * h_pos + gw @ h_neg
                    neg_slots = jnp.where(n_valid.any(axis=0), n_slots, -1)
                    # pool rows push as their own SUM family; see the
                    # normalization-collapse note in _build_grads_shared
                    pushes = (PushSpec(c_slots, {"h": gh_pos}, mean=True),
                              PushSpec(neg_slots, {"h": gh_neg}),
                              v_push(span_slots, bwd, at_positions,
                                     neu1e))
                    ratio = self.negative / K
                    err_sum = jnp.sum(1e4 * g_pos * g_pos) \
                        + ratio * jnp.sum(1e4 * g_neg * g_neg)
                    err_cnt = row_valid.sum() + ratio * n_valid.sum()
                return pushes, err_sum, err_cnt
            with obs.named_scope("sample"):
                # parity negatives: per-center draws from the SAME sampling
                # stream as _cbow_targets — the oracle test's anchor
                negs, neg_slots = _negative_slots(
                    key, alias_prob, alias_idx, slot_of_vocab, (B, K), take)
                t_slots = jnp.concatenate(
                    [c_slots[:, None], neg_slots], axis=1)    # (rows, K+1)
                t_valid = jnp.concatenate(
                    [jnp.ones((rows, 1), bool), negs != c_words[:, None]],
                    axis=1)
                t_valid = t_valid & row_valid[:, None]
                t_slots = jnp.where(t_valid, t_slots, -1)
            with obs.named_scope("math"):
                h_t = transfer.pull(
                    state, t_slots.reshape(-1), access, fields=("h",)
                )["h"].reshape(rows, K + 1, d).astype(jnp.float32)
                f = jnp.einsum("bd,bkd->bk", neu1, h_t)
                labels = jnp.concatenate(
                    [jnp.ones((rows, 1)), jnp.zeros((rows, K))], axis=1)
                g = (labels - sigmoid_clipped(f)) * alpha
                g = jnp.where(t_valid, g, 0.0)                   # (B, K+1)
                h_contrib = g[..., None] * neu1[:, None, :]      # (B,K+1,d)
                neu1e = jnp.einsum("bk,bkd->bd", g, h_t)         # (B, d)
                # v first, as _assemble_push has it: the order of the two
                # pushes moves the step's peak memory, no value
                pushes = (v_push(span_slots, bwd, at_positions, neu1e),
                          PushSpec(t_slots.reshape(-1),
                                   {"h": h_contrib.reshape(-1, d)},
                                   mean=True))
                err_sum = jnp.sum(1e4 * g * g)          # word2vec.h:593
                err_cnt = t_valid.sum()
            return pushes, err_sum, err_cnt

        return grads_fn

    def _build_grads_sg(self):
        """Skip-gram gradient phase.  Pair axis (B, 2W): input v[context],
        targets h[center]+K negatives sampled fresh *per pair* (word2vec.c
        semantics; the reference's learn_instance is the CBOW specialization
        of the same loop, word2vec.h:550-615).  Masked pairs (window
        padding) contribute nothing."""
        access = self.access
        transfer = self.transfer
        K = self.negative
        alpha = self.alpha
        d = self.row_width

        def grads_fn(state, slot_of_vocab, alias_prob, alias_idx,
                     centers, contexts, ctx_mask, key):
            B, W2 = contexts.shape
            with obs.named_scope("sample"):
                negs, neg_slots = _negative_slots(
                    key, alias_prob, alias_idx, slot_of_vocab, (B, W2, K))
                # negative == center is skipped (word2vec.h:584-586); padding
                # pairs are fully dead.
                t_valid = jnp.concatenate(
                    [jnp.ones((B, W2, 1), bool),
                     negs != centers[:, None, None]], axis=2)
                t_valid = t_valid & ctx_mask[..., None]
                c_slots = jnp.broadcast_to(
                    slot_of_vocab[centers][:, None, None], (B, W2, 1))
                t_slots = jnp.where(
                    t_valid, jnp.concatenate([c_slots, neg_slots], axis=2), -1)
                ctx_slots = jnp.where(ctx_mask, slot_of_vocab[contexts], -1)

            with obs.named_scope("math"):
                h_t = transfer.pull(
                    state, t_slots.reshape(-1), access, fields=("h",)
                )["h"].reshape(B, W2, K + 1, d).astype(jnp.float32)
                v_in = transfer.pull(
                    state, ctx_slots.reshape(-1), access, fields=("v",)
                )["v"].reshape(B, W2, d).astype(jnp.float32)

                f = jnp.einsum("bwd,bwkd->bwk", v_in, h_t)
                labels = jnp.concatenate(
                    [jnp.ones((B, W2, 1)), jnp.zeros((B, W2, K))], axis=2)
                g = (labels - sigmoid_clipped(f)) * alpha
                g = jnp.where(t_valid, g, 0.0)  # (B, W2, K+1)

                h_contrib = g[..., None] * v_in[:, :, None, :]  # (B,W2,K+1,d)
                v_contrib = jnp.einsum("bwk,bwkd->bwd", g, h_t)   # (B, W2, d)
                v_contrib = jnp.where(ctx_mask[..., None], v_contrib, 0.0)

                pushes = _assemble_push(
                    t_slots.reshape(-1), ctx_slots.reshape(-1),
                    h_contrib.reshape(-1, d), v_contrib.reshape(-1, d))

                err_sum = jnp.sum(1e4 * g * g)          # word2vec.h:593
                err_cnt = t_valid.sum()
            return pushes, err_sum, err_cnt

        return grads_fn

    def _build_grads_sg_shared(self):
        """Skip-gram with a batch-shared negative pool (opt-in,
        ``sg: 1`` + ``shared_negatives: 1``) — the TPU-first rendering
        of BASELINE config #2's per-pair sampler.

        The parity sg phase draws K negatives per PAIR
        (word2vec.h:550-615 semantics), a B*2W*(K+1)-row random target
        gather.  Measured at 300 wide on a table that fills a v5e chip
        (benchmark cell ``sg2m-b2k``, 2,048 centers a step; PERF.md
        section 5, chip runs of PR 26): that per-pair work is 14.5 ms of
        a 175.5 ms step (``dedup`` 7.16, ``pull`` 3.07, ``math`` 2.73,
        ``sample`` 1.58); the rest follows the table's size, not the
        batch, in every rendering.  Sharing one
        K-negative pool across every pair in the batch keeps the same
        expected negative-term gradient (each pool pair weighted
        negative/K, the `_build_grads_shared` argument) and collapses
        the target gather to B + K rows:

          h gather:  B centers + K pool   instead of B*2W*(K+1)
          f_neg:     einsum (B,2W,d)x(K,d) -> (B,2W,K)   — MXU matmul
          gh_neg:    einsum (B,2W,K)x(B,2W,d) -> (K,d)   — DENSE, no
                     scatter for the pool at all
          v grads:   g_pos*h[center] + gw @ h_neg        — matmul

        Positive pairs and context rows keep per-key mean
        normalization; pool rows push as their own SUM family (the
        normalization-collapse hazard documented in
        _build_grads_shared applies identically here).  NOT loss-parity
        with the reference RNG stream — the parity sg mode stays the
        default; benches label this rendering ``sg_shared``."""
        access = self.access
        transfer = self.transfer
        K = self.shared_pool
        alpha = self.alpha
        d = self.row_width

        def grads_fn(state, slot_of_vocab, alias_prob, alias_idx,
                     centers, contexts, ctx_mask, key):
            B, W2 = contexts.shape
            with obs.named_scope("sample"):
                negs = sample_alias(key, alias_prob, alias_idx, (K,))
                c_slots = slot_of_vocab[centers]                  # (B,)
                n_slots = slot_of_vocab[negs]                     # (K,)
                ctx_slots = jnp.where(ctx_mask, slot_of_vocab[contexts], -1)

            with obs.named_scope("math"):
                pulled_h = transfer.pull(
                    state, jnp.concatenate([c_slots, n_slots]), access,
                    fields=("h",))["h"].astype(jnp.float32)
                h_pos = pulled_h[:B]                              # (B, d)
                h_neg = pulled_h[B:B + K]                         # (K, d)
                v_in = transfer.pull(
                    state, ctx_slots.reshape(-1), access, fields=("v",)
                )["v"].reshape(B, W2, d).astype(jnp.float32)

                # positive pair (b, w): v[context] . h[center_b]
                f_pos = jnp.einsum("bwd,bd->bw", v_in, h_pos)     # (B, W2)
                g_pos = (1.0 - sigmoid_clipped(f_pos)) * alpha
                g_pos = jnp.where(ctx_mask, g_pos, 0.0)

                f_neg = jnp.einsum("bwd,kd->bwk", v_in, h_neg)    # MXU
                # negative == center skipped (word2vec.h:584-586); padding
                # pairs are fully dead
                n_valid = (negs[None, None, :] != centers[:, None, None]) \
                    & ctx_mask[..., None]
                g_neg = jnp.where(n_valid,
                                  (0.0 - sigmoid_clipped(f_neg)) * alpha, 0.0)
                # keep the objective's positive/negative balance at the
                # configured `negative` draws per pair
                gw = g_neg * (self.negative / K)                  # (B, W2, K)

                # per-pair positive grads -> h[center], per-key mean (same
                # normalization the parity sg push applies per pair)
                gh_pos = g_pos[..., None] * v_in                  # (B, W2, d)
                gh_neg = jnp.einsum("bwk,bwd->kd", gw, v_in)      # (K, d) MXU
                v_contrib = g_pos[..., None] * h_pos[:, None, :] \
                    + gw @ h_neg                                  # (B, W2, d)
                v_contrib = jnp.where(ctx_mask[..., None], v_contrib, 0.0)

                pos_slots = jnp.where(
                    ctx_mask, jnp.broadcast_to(c_slots[:, None], (B, W2)), -1)
                neg_slots = jnp.where(n_valid.any(axis=(0, 1)), n_slots, -1)
                pushes = (PushSpec(pos_slots.reshape(-1),
                                   {"h": gh_pos.reshape(-1, d)}, mean=True),
                          PushSpec(neg_slots, {"h": gh_neg}),
                          PushSpec(ctx_slots.reshape(-1),
                                   {"v": v_contrib.reshape(-1, d)}, mean=True))

                # loss terms carry the SAME negative/K weighting as the
                # gradients (advisor r04): a pair contributes ~1 positive +
                # ~`negative` weighted pool terms, so the reported loss is
                # scale-comparable with the per-pair parity sg rendering
                # instead of ~K/negative times off
                ratio = self.negative / K
                err_sum = jnp.sum(1e4 * g_pos * g_pos) \
                    + ratio * jnp.sum(1e4 * g_neg * g_neg)
                err_cnt = ctx_mask.sum() + ratio * n_valid.sum()
            return pushes, err_sum, err_cnt

        return grads_fn

    def _build_async_grads(self):
        """The gradient program of the async ``(grads, apply)`` pair, in
        the step programs' form (`_carried`): pushes computed against a
        state that is NOT donated (the stale snapshot), the key and the
        tally taken and returned."""
        grads_fn = self._build_grads()

        @partial(jax.jit, **self._carried_jit(
            donate=("key", "tally"),
            static_argnames="centers" if self.stencil else None))
        def grads(state, *args, key, tally=None, **shape):
            return _carried(key, tally, lambda sub: grads_fn(
                state, *args, sub, **shape))

        return grads

    def _build_apply(self):
        access = self.access
        transfer = self.transfer

        def apply_fn(state, pushes):
            for spec in pushes:
                if getattr(spec, "counts", None) is not None:
                    # position-indexed span family (stencil rendering):
                    # rows are pre-summed, their data counts the mean's
                    # multiplicity
                    state = transfer.push_span(
                        state, spec.slots, spec.grads, spec.counts,
                        access, mean=spec.mean)
                else:
                    state = transfer.push(state, spec.slots, spec.grads,
                                          access, mean=spec.mean)
            return state

        return apply_fn

    # -- training (word2vec.h:475-547) -------------------------------------
    def _epoch_items(self, batcher, batch_size: int, stencil: bool,
                     fuse: bool, pairs: Optional[_PairCount] = None):
        """Render one epoch into a stream of work items: ``('group',
        host-stacked fields, [n_words...])`` for fuse groups and
        ``('single', fields, n_words)`` otherwise.  Pure host-side
        rendering — NO RNG (key splits stay with the consumer, in
        consumption order) and no device calls — so the stream is
        identical whether it is consumed inline or through the
        prefetch pipeline: the determinism contract of
        ``[worker] pipeline``."""
        inner = self.inner_steps
        group = []
        # control-plane frequency sketch: observe the center/token ids
        # HERE, on the rendering side (host numpy, thread-safe observe)
        # — consumption may see already-transferred device arrays when
        # the pipeline is on
        sketch = self._control_sketch

        def observe(fields):
            # a span batch is one packed buffer: its tokens lead it
            if sketch is not None:
                sketch.observe(unpack_span(fields[0], batch_size)[0]
                               if stencil else fields[0])
            if pairs is None:
                return
            if stencil:
                pairs.observe_span(fields[0], batch_size, self.window)
            else:
                pairs.observe(fields[2])

        def group_item():
            n_words = [b.n_words for b in group]
            fields = (_stack_group_host_stencil(group) if stencil
                      else _stack_group_host(group))
            observe(fields)
            return ("group", fields, n_words)

        epoch_iter = (batcher.epoch_stencil(batch_size) if stencil
                      else batcher.epoch(batch_size))
        for batch in epoch_iter:
            # every stencil batch is fixed-shape (padded span), so all
            # of them group-fuse, tails included
            if fuse and (stencil or len(batch.centers) == batch_size):
                group.append(batch)
                if len(group) == inner:
                    yield group_item()
                    group = []
                continue
            # odd-shaped batch: flush pending fused batches first so
            # the update order matches the unfused loop
            if group:
                yield group_item()
                group = []
            if stencil:
                fields = (batch.pack(),)
            else:
                fields = (batch.centers, batch.contexts,
                          batch.ctx_mask)
            observe(fields)
            yield ("single", fields, batch.n_words)
        if group:                  # leftover partial group
            yield group_item()

    def train(self, data=None, niters: int = 1,
              batch_size: Optional[int] = None,
              checkpoint_path: Optional[str] = None,
              checkpoint_every: int = 1,
              checkpoint_retain: int = 1,
              start_iter: int = 0,
              batcher=None) -> List[float]:
        """``data``: corpus path or list of key-list sentences.  Returns
        per-iteration mean error (reference Error::norm per train_iter,
        word2vec.h:491).

        ``checkpoint_path``: mid-training full-fidelity checkpoints
        (optimizer state included) every ``checkpoint_every`` iterations —
        a capability the reference lacks (SURVEY.md §5: checkpoint-out only
        at exit, optimizer state dropped).  Resume with ``resume()``.
        ``checkpoint_retain`` keeps a last-k generation window on disk so
        a corrupted latest checkpoint can rewind (io/checkpoint.py).

        Every iteration reports to the fault/observability bus
        (``testing.faults.step_event``) — chaos plans and the resume
        loop's hang watchdog both hook there.

        ``batcher``: custom batch source with an ``epoch(batch_size)``
        iterator (e.g. the native C++ ``NativeCBOWBatcher``); its vocab
        indexing must match this model's vocab (both pipelines sort by
        (count desc, key asc), so python- and native-built vocabs agree)."""
        # host spans of the whole call (obs.catalog.HOST_SPANS):
        # train_setup runs from here to the first next(items)
        setup_span = obs.span("train_setup")
        setup_span.__enter__()
        setup_seen = obs.get_registry().enabled
        if batcher is None:
            if isinstance(data, str):
                data = load_corpus(data, min_sentence_length=max(
                    self.min_sentence_length, 1))
            if data is None:
                raise ValueError("train() needs data or a batcher")
            if self.vocab is None:
                self.build(data)
        elif self.vocab is None:
            if hasattr(batcher, "vocab"):
                self.build_from_vocab(batcher.vocab)
            else:
                raise RuntimeError(
                    "call build()/build_from_vocab() before train() with a "
                    "vocab-less batcher")
        hogwild = self.async_mode == "hogwild"
        nprocs = jax.process_count()
        if hogwild and nprocs > 1:
            # hogwild's worker axis spans ONE process's devices; the
            # measured multi-host substitute is the snapshot bounded-
            # staleness mode — loss envelope within +0.02% of hogwild
            # at realistic scale (docs/ARCHITECTURE.md "Async modes"),
            # so route there with a notice instead of refusing the run
            self.local_steps = max(self.local_steps, 2)
            log.warning(
                "async_mode=hogwild spans a single process's devices; "
                "multi-process run falls back to snapshot bounded "
                "staleness (local_steps=%d; measured loss envelope "
                "+0.02%% vs hogwild at realistic scale — see "
                "docs/ARCHITECTURE.md)", self.local_steps)
            hogwild = False
        stencil = self._settle_stencil(batcher)
        if stencil and hogwild:
            raise ValueError(
                "async_mode=hogwild drives per-pair batches; the stencil "
                "rendering composes with the snapshot (local_steps) "
                "async mode instead")
        if stencil and nprocs > 1:
            raise ValueError(
                "the stencil rendering is single-process for now "
                "(DistributedBatcher shards per-pair batches); drop "
                "stencil or run single-process")
        sync = self.local_steps <= 1 and not hogwild
        # fused multi-step only makes sense single-process (distributed
        # batches are global arrays that cannot be host-stacked)
        fuse = sync and self.inner_steps > 1 and nprocs == 1
        batch_size = batch_size or max(
            256, self.minibatch // (2 * self.window))
        if batcher is None:
            sents = data
            seed = 2008
            if nprocs > 1:
                # per-rank data shard + rank-decorrelated sampling: the
                # reference's "one file per node" distribution
                from swiftmpi_tpu.data.distributed import shard_sentences
                sents = shard_sentences(data)
                seed += jax.process_index()
            batcher = CBOWBatcher(sents, self.vocab, self.window,
                                  self.sample, seed=seed)
        if nprocs > 1:
            from swiftmpi_tpu.data.distributed import DistributedBatcher
            if not isinstance(batcher, DistributedBatcher):
                batcher = DistributedBatcher(batcher, self.cluster.mesh)
        # serving plane ([serve] every, serve/): arm the snapshot
        # publisher so concurrent EmbeddingReaders can pull bounded-
        # staleness views while this loop trains
        if self.serve_every > 0:
            self.serving_publisher()
        state = self.table.state
        frozen = state   # stale snapshot for the async mode
        losses = []
        meter = Throughput()
        step_i = 0
        hogwild_dropped = 0
        rows_written = tiles_written = tile_copies = rows_steps = 0  # sync
        routed_rows = routed_slots = routed_steps = 0       # ... routed
        # telemetry plane ([worker] telemetry, obs/): reuse an outer
        # recorder (bench harness, trainer) or own one for this call.
        # The Throughput meter and transfer ledger keep their own
        # cumulative state, so they bridge into the registry through a
        # pre-snapshot sampler (set_total keeps the counters monotonic).
        tel_rec = obs.get_recorder()
        owns_rec = tel_rec is None
        if owns_rec:
            tel_rec = obs.configure(self.config, run="word2vec")
            if not setup_seen and obs.get_registry().enabled:
                # the process's first call: configure armed the plane just
                # now, so this call's span can only open after it
                setup_span = obs.span("train_setup")
                setup_span.__enter__()
        if tel_rec is not None:
            def _tel_sample(reg, _m=meter):
                reg.counter("train/host_stall_ms_total").set_total(
                    _m.host_stall_ms())
                reg.gauge("train/words_per_sec").set(_m.rate())
            tel_rec.add_sampler(_tel_sample)
        # pair counters: host sums over the batches as rendered, only
        # with telemetry on (no sum is taken otherwise)
        pairs = _PairCount(obs.get_registry()) \
            if obs.get_registry().enabled else None
        if self.numerics_on and tel_rec is not None:
            self._arm_numerics(tel_rec)
        # wire tracer hot-key attribution ([obs] trace): the control
        # sketch's decayed counts replace the reservoir touch estimates.
        # build() armed control before obs.configure installed the
        # tracer, so the attach happens here too.
        _tracer = obs.get_tracer()
        if _tracer is not None and self._control_sketch is not None:
            _tracer.attach_sketch(self._control_sketch)
        # The tracer's window records are fed from the wire ledger's
        # landing points, which are behind the count_traffic opt-in
        # (one extra host reduce per push, no traced-value change) —
        # arm it so `[obs] trace: 1` records through the CLI without a
        # second knob.
        if _tracer is not None and hasattr(self.transfer, "count_traffic"):
            self.transfer.count_traffic = True
        self._settle_key()
        # step compile AFTER numerics arming: the builders close over
        # self._numerics at trace time, and a first-time arm drops any
        # step compiled without the bundle
        if self._step is None:
            self._fused_cache = {}
            self._step = self._make_step(hogwild)
        # -- input pipeline setup (tentpole: prefetch-rendered,
        # pre-transferred batches).  The producer is gated to paths
        # where it can own rendering wholesale: hogwild does its own
        # grouping, and multi-process batches are global jax.Arrays
        # already placed by DistributedBatcher.
        pipelined = (self.pipeline_depth > 0 and not hogwild
                     and nprocs == 1)
        if self.pipeline_depth > 0 and not pipelined:
            log.warning(
                "[worker] pipeline=%d requested but %s — running the "
                "synchronous input loop", self.pipeline_depth,
                "hogwild groups its own batches" if hogwild
                else "multi-process batches are already-placed global "
                     "arrays")
        dispatch_bound = resolve_dispatch_bound(self.dispatch_depth,
                                                pipelined=pipelined)
        transfer_fn = None
        pipe_stats = None
        if pipelined:
            from swiftmpi_tpu.io.pipeline import (PrefetchIterator,
                                                  device_put_transfer)
            # committed replicated input sharding, captured HERE on the
            # consumer thread: jax.default_device is thread-local and
            # must never be consulted by the producer
            input_sharding = jax.sharding.NamedSharding(
                self.cluster.mesh, jax.sharding.PartitionSpec())
            transfer_fn = device_put_transfer(input_sharding)
            pipe_stats = {"produced": 0, "consumed": 0,
                          "peak_queue_depth": 0, "stall_s": 0.0,
                          "transfer_s": 0.0}
        # a span step cuts its packed batch by the centers it holds
        shape = {"centers": batch_size} if stencil else {}

        def end_setup():
            nonlocal setup_span
            if setup_span is not None:
                setup_span.__exit__(None, None, None)
                setup_span = None

        def close_epoch(it, err_sum, err_cnt):
            loss = err_sum / max(err_cnt, 1)
            losses.append(loss)
            log.info("iter %d: error %.5f  (%.0f words/s)",
                     it, loss, meter.rate())

        def to_device(fields):
            if pipelined:      # the producer thread put them: its `h2d`
                return tuple(_dev(f) for f in fields)
            with obs.span("h2d"):
                return tuple(_dev(f) for f in fields)

        for it in range(niters):
            # global step: cumulative across resumed runs, so a fault
            # plan's crash-at-step-k means "after k completed steps"
            # regardless of how many attempts it took to get there
            faults.step_event(start_iter + it)
            if faults.consume_nan():
                state = self._poison_row(state)
                frozen = state
            if hogwild:
                end_setup()
                err_sum, err_cnt, it_dropped = self._hogwild_epoch(
                    batcher, batch_size, meter)
                hogwild_dropped += it_dropped
                state = self.table.state
                # hogwild groups its own dispatches; publish at epoch
                # granularity (the mode's natural consistency point)
                self._serve_on_steps(1)
                close_epoch(it, err_sum, err_cnt)
            else:
                # The epoch's sums ride in the step program (`_Tally`):
                # every step takes the tally and returns it, and the
                # epoch ends in one wait and one read — a float(es) per
                # batch is a blocking round trip that serializes
                # dispatch, and a queue of per-step scalars is a launch
                # a scalar to convert and a stack-and-sum to fetch.
                tally = self._carry(_Tally.zeros())
                # the async pair is two programs a step
                window = DispatchWindow(dispatch_bound,
                                        programs=1 if sync else 2)
                steps_counted = 0      # sync single steps: they count rows

                # an item of the loop is input_wait, then four siblings:
                # step_prep, h2d (to_device), dispatch, step_book

                def run_single(fields, n_words):
                    nonlocal state, frozen, step_i, tally, steps_counted
                    n = self._steps_dispatched
                    with obs.span("step_prep", step=n):
                        statics = (self._slot_of_vocab, self._alias_prob,
                                   self._alias_idx)
                    args = (*statics, *to_device(fields))
                    with obs.span("dispatch", steps=1, step=n):
                        if sync:
                            state, key, tally, es = self._step(
                                state, *args, self._key, tally, **shape)
                        else:
                            # async/global variant, bounded-staleness
                            # flavor (word2vec_global.h:577-651): grads
                            # computed against a stale snapshot, pushes
                            # land immediately; snapshot refreshes every
                            # local_steps batches => bounded staleness.
                            grads_fn, apply_fn = self._step
                            pushes, key, tally, es = grads_fn(
                                frozen, *args, key=self._key, tally=tally,
                                **shape)
                            state = apply_fn(state, pushes)
                    with obs.span("step_book", step=n):
                        # the step donates (deletes) the input state
                        # buffers, and the key's; repoint the table and
                        # the key at the live ones immediately so an
                        # abnormal exit (raise, Ctrl-C) never strands the
                        # model with deleted arrays
                        self.table.state = state
                        self._rekey(key)
                        window.push(es)
                        if not sync:
                            step_i += 1
                            if step_i % self.local_steps == 0:
                                frozen = state
                        steps_counted += sync
                        self._steps_dispatched += 1
                        meter.record(n_words)
                        obs.record_step(1)
                        self._serve_on_steps(1)
                        if self._control_on_steps(1):
                            # an applied decision re-laid out the table
                            # (or rebuilt the step): repoint the
                            # loop-local state — and the async snapshot,
                            # whose rows sit at pre-repartition slots —
                            # at the remapped one
                            state = self.table.state
                            frozen = state
                        # the step's inputs are let go inside the span,
                        # not with this frame after it
                        del args, key, es

                def run_group(fields, n_words):
                    # update ORDER is preserved either way: a group runs
                    # its batches sequentially inside one scan dispatch.
                    # Partial groups (the epoch tail) fuse too, via the
                    # per-length compiled cache — a small corpus's epoch
                    # is a handful of batches, and dispatching them
                    # one-by-one pays the per-dispatch overhead each
                    # (not measured on this stack).  A lone batch uses the already-
                    # compiled single step.
                    nonlocal state, tally
                    L = len(n_words)
                    if L == 1:
                        # lone batch: peel the stacked fields back
                        # into a single
                        run_single(tuple(f[0] for f in fields),
                                   n_words[0])
                        return
                    n = self._steps_dispatched
                    with obs.span("step_prep", step=n):
                        fused = self._fused_for(L)
                    fields = to_device(fields)
                    with obs.span("dispatch", steps=L, step=n):
                        state, key, tally, es = fused(
                            state, self._slot_of_vocab, self._alias_prob,
                            self._alias_idx, *fields, self._key, tally,
                            **shape)
                    with obs.span("step_book", step=n):
                        self.table.state = state
                        self._rekey(key)
                        window.push(es)
                        self._steps_dispatched += L
                        # a fused group is ONE dispatch but L train steps;
                        # stall_ms_per_step stays per-step across fuse modes
                        meter.record(sum(n_words), steps=L)
                        obs.record_step(L)
                        self._serve_on_steps(L)
                        if self._control_on_steps(L):
                            state = self.table.state

                items = self._epoch_items(batcher, batch_size, stencil,
                                          fuse, pairs)
                pipe = None
                if pipelined:
                    pipe = PrefetchIterator(
                        items, depth=self.pipeline_depth,
                        transfer=transfer_fn)
                    items = pipe
                try:
                    items = iter(items)
                    end_setup()
                    while True:
                        # the stall clock covers exactly the input
                        # wait: inline it times rendering + stacking,
                        # pipelined it times empty-queue waits — one
                        # meter for both, so host_stall_ms is directly
                        # comparable across the two modes.  The
                        # `input_wait` span likewise: the pipelined path
                        # has it inside PrefetchIterator.__next__, one
                        # sample per item in either mode
                        with meter.stalling():
                            if pipelined:
                                nxt = next(items, None)
                            else:
                                with obs.span("input_wait") as wait:
                                    nxt = next(items, None)
                                    if nxt is None:
                                        wait.drop()
                        if nxt is None:
                            break
                        kind, fields, n_words = nxt
                        if kind == "group":
                            run_group(fields, n_words)
                        else:
                            run_single(fields, n_words)
                finally:
                    if pipe is not None:
                        pipe.close()
                        for k, v in pipe.stats().items():
                            if k == "peak_queue_depth":
                                pipe_stats[k] = max(pipe_stats[k], v)
                            elif k != "depth":
                                pipe_stats[k] += v
                # the epoch's one blocking fetch: loss_wait for the
                # newest tally (every step ran), its one read, and the
                # epoch's line in the log
                with obs.span("loss_fetch"):
                    with obs.span("loss_wait"):
                        jax.block_until_ready(tally)
                    window.clear()
                    sums = _Tally.read(tally)
                    del tally
                    if sums["rows"]:       # a step built to count them
                        rows_written += sums["rows"]
                        tiles_written += sums["tiles"]
                        tile_copies += sums["copies"]
                        rows_steps += steps_counted
                    if sums["offered"]:    # a routed step offers slots
                        routed_rows += sums["routed"]
                        routed_slots += sums["offered"]
                        routed_steps += steps_counted
                    close_epoch(it, sums["err"], sums["pair_count"])
            if checkpoint_path and (it + 1) % checkpoint_every == 0:
                self.table.state = state
                from swiftmpi_tpu.io.checkpoint import (npz_path,
                                                        save_checkpoint)
                # cumulative iteration: a resumed run must not rewind the
                # counter, or a later resume re-trains finished iters
                ck_extra = {"iter": np.int64(start_iter + it + 1)}
                if self._numerics is not None \
                        and self._numerics.detector is not None:
                    # baselines ride along so a resumed run scores its
                    # first windows against the learned regime instead
                    # of re-warming (and false-alarming) from scratch
                    self._numerics.sync(obs.get_registry())
                    ck_extra["numerics"] = \
                        self._numerics.detector.state_bytes()
                save_checkpoint(
                    self.table, checkpoint_path,
                    extra=ck_extra,
                    retain=checkpoint_retain)
                log.info("checkpoint @ iter %d -> %s", start_iter + it + 1,
                         checkpoint_path)
                faults.checkpoint_event(npz_path(checkpoint_path))
        end_setup()       # niters == 0: no epoch closed it
        prof = obs.get_profiler()
        if prof is not None:
            # training ended inside a capture window: stop the trace and
            # land the summary artifact anyway (short runs, profile_at
            # near the end) — before the recorder that takes its event
            # closes, and outside train_finish: it compiles phase maps
            prof.close()
        with obs.span("train_finish"):
            self.table.state = state
            # final publish: readers see the trained state no matter where
            # the every-K cadence landed
            self._serve_publish()
            # observability surface (returned data, not just logs): the
            # hogwild drop bound is testable and the hybrid backend's
            # traffic counters ride along for bench detail fields
            self.train_metrics = {
                "hogwild_skipped_tail_words": hogwild_dropped,
                # time the loop spent waiting on input
                # (utils.timers.Throughput)
                "host_stall_ms": meter.host_stall_ms(),
                "stall_ms_per_step": meter.stall_ms_per_step(),
                "words_per_sec": meter.rate(),
                "pipeline_depth": self.pipeline_depth if pipelined else 0}
            if rows_steps:
                self.train_metrics["rows_written_per_step"] = \
                    rows_written / rows_steps
                # 0: no push of the step took the tile kernel
                self.train_metrics["tiles_written_per_step"] = \
                    tiles_written / rows_steps
                # ... and the copies that moved them one way: its runs of
                # adjacent tiles (== the tiles where none has a neighbour)
                self.train_metrics["tile_copies_per_step"] = \
                    tile_copies / rows_steps
            if routed_steps:
                # what the transfer sent to the rows' owners, and how much
                # of the bucket slots it exchanged for that was rows
                self.train_metrics["routed_rows_per_step"] = \
                    routed_rows / routed_steps
                self.train_metrics["route_fill_share"] = \
                    100.0 * routed_rows / max(routed_slots, 1)
            if pairs is not None and pairs.steps:
                self.train_metrics["pairs_per_step"] = \
                    pairs.valid / pairs.steps
                self.train_metrics["pair_fill_share"] = \
                    100.0 * pairs.valid / pairs.grid
                if pairs.span_rows:
                    self.train_metrics["span_rows_per_step"] = \
                        pairs.span_rows / pairs.steps
            if pipe_stats is not None:
                self.train_metrics["pipeline"] = dict(pipe_stats)
            if self.controller is not None:
                self.train_metrics["control"] = {
                    **self.controller.summary(),
                    "recompiles": self._control_recompiles}
            if hasattr(self.transfer, "traffic"):
                # traffic() drains queued eager counts through _accum_wire,
                # so the registry mirror is exact before the summary lands
                self.train_metrics["transfer_traffic"] = \
                    self.transfer.traffic()
            if self._numerics is not None:
                # drain in-flight bundle callbacks (safe point: dispatches
                # retired), then disarm the process-global quant tap — a
                # numerics-off model training next in this process must
                # trace (and book) nothing
                from swiftmpi_tpu.transfer import api as transfer_api
                self._numerics.sync(obs.get_registry())
                transfer_api.clear_numerics_tap()
                det = self._numerics.detector
                self.train_metrics["numerics"] = {
                    "bundles": self._numerics.bundles,
                    "anomalies": det.anomalies_emitted if det else 0}
            if owns_rec and tel_rec is not None:
                tel_rec.close()
                obs.uninstall_recorder()
                # its ring of step records goes here, inside the span,
                # not with the frame after it
                tel_rec = None
        obs.log_setup_once()     # start-up, said once a process
        return losses

    def sampling_state(self):
        """``(step_key, alias_prob, alias_idx)``: the key the next train
        step will draw its negatives with (what ``train()`` splits off
        ``_key`` for it; the call leaves the stream where it is) and the
        sampler's alias tables over the vocabulary, so a caller can make
        the step's draw again (``ops.sampling.sample_alias``) without
        reaching for the private names."""
        return (jax.random.split(self._key)[1], self._alias_prob,
                self._alias_idx)

    def _hogwild_epoch(self, batcher, batch_size: int, meter) -> tuple:
        """One epoch in hogwild mode: group ``n_workers * local_steps``
        fixed-shape batches per dispatch, one per worker-step.  A tail
        too short for a full group is dropped, logged, AND returned (the
        third element of the result; summed into
        ``train_metrics["hogwild_skipped_tail_words"]``).  Workers in
        the reference's async mode likewise end an iteration unevenly —
        word2vec_global.h:630-651 joins threads wherever they ran out.

        Drop bound: per epoch at most ``group - 1`` full batches plus
        the partial batches the batcher emits — under
        ``group * batch_size * (1 + 2*window)`` words, a vanishing
        fraction of any corpus large enough to satisfy the no-group
        RuntimeError below.  The documented-drop-bound route is chosen
        over pad+mask, which would compile a second (padded) step shape
        per epoch to recover that fraction."""
        step, n_workers = self._step
        group = n_workers * max(self.local_steps, 1)
        state = self.table.state
        tally = self._carry(_Tally.zeros())
        window = DispatchWindow()
        buf = []
        dropped = 0
        for batch in batcher.epoch(batch_size):
            if len(batch.centers) != batch_size:
                dropped += batch.n_words
                continue
            buf.append(batch)
            if len(buf) < group:
                continue
            c, x, m = _stack_group(buf)
            state, key, tally, es = step(
                state, self._slot_of_vocab, self._alias_prob,
                self._alias_idx, c, x, m, self._key, tally)
            self.table.state = state
            self._rekey(key)
            window.push(es)
            meter.record(sum(b.n_words for b in buf), steps=len(buf))
            obs.record_step(len(buf))
            buf = []
        if buf:
            dropped += sum(b.n_words for b in buf)
        sums = _Tally.read(tally)
        err_sum, err_cnt = sums["err"], sums["pair_count"]
        if err_cnt == 0:
            raise RuntimeError(
                f"hogwild epoch dispatched NO group: the corpus yielded "
                f"fewer than {group} full batches of {batch_size} centers "
                f"(group = {group // max(self.local_steps, 1)} workers x "
                f"{max(self.local_steps, 1)} local_steps).  Lower "
                f"batch_size/local_steps or use more data — otherwise the "
                f"run would silently train nothing")
        if dropped:
            log.info("hogwild: %d tail words skipped this iter (need "
                     "full groups of %d batches x %d centers)",
                     dropped, group, batch_size)
        return err_sum, err_cnt, dropped

    def grow(self, new_capacity_per_shard: int) -> None:
        """Mid-run table growth (reference dense_hash_map self-growth,
        sparsetable.h:17-149 — here an explicit HBM re-layout).  Owns the
        post-grow fixups a bare ``table.grow()`` would leave stale: the
        jitted step bakes in the old capacity (the push scatter
        bounds), and the cached vocab->slot map holds old-layout slots —
        either one silently corrupts scatters if kept."""
        self.table.grow(new_capacity_per_shard)
        self._step = None
        if self.vocab is not None:
            slots = self.table.key_index.lookup(self.vocab.keys)
            self._slot_of_vocab = jnp.asarray(slots, jnp.int32)

    def resume(self, checkpoint_path: str) -> int:
        """Restore a mid-training checkpoint; returns the iteration it was
        taken at.  The cached vocab->slot map is rebuilt against the
        restored key index so continued training touches the right rows
        even if the checkpoint's slot assignment differs from build()'s."""
        from swiftmpi_tpu.io.checkpoint import load_checkpoint
        if self.table is None:
            raise RuntimeError("build() or load() the model before resume()")
        extra = load_checkpoint(self.table, checkpoint_path)
        # load_checkpoint grows the table for post-grow() checkpoints; any
        # cached jitted step baked in the old capacity (the push
        # scatter bounds), so force a rebuild
        self._step = None
        # a restore can rewind the @rowver plane; a warm pull cache
        # could then false-hit on a re-used version stamp.  A resumed
        # worker always restarts cold (pull_cache.py invalidation
        # contract; the chaos test pins this).
        self.transfer.pull_shadow_flush()
        if self.vocab is not None:
            slots = self.table.key_index.lookup(self.vocab.keys)
            self._slot_of_vocab = jnp.asarray(slots, jnp.int32)
        num_state = extra.get("numerics")
        if num_state is not None:
            # detector baselines ride the checkpoint (ISSUE 13): loaded
            # now if the plane is already armed, else stashed for
            # _arm_numerics — either way the first post-restore window
            # scores against the learned regime, not a cold baseline
            if self._numerics is not None \
                    and self._numerics.detector is not None:
                self._numerics.detector.load_state_bytes(num_state)
            else:
                self._numerics_restore = num_state
        return int(extra.get("iter", 0))

    # -- embeddings out/in (word2vec.h:100-117; cluster.h:41-54) -----------
    def save(self, path: str) -> int:
        # reference WParam layout: v TAB h (word2vec.h:100-110); fields mode
        # routes through the native C++ writer when available
        return dump_table_text(self.table, path, fields=("v", "h"))

    def load(self, path: str) -> int:
        if self.table is None:
            if self._capacity_per_shard is None:
                raise RuntimeError("set capacity_per_shard before load()")
            self.table = self.cluster.create_table(
                "w2v", self.access, self._capacity_per_shard)
        n = load_table_text(self.table, path, fields=("v", "h"))
        self._step = None    # text load may have grown the table
        if self.vocab is not None:
            # growth remaps slots (KeyIndex.grow re-lays out
            # shard*cap+local); a stale cached map would make
            # embedding_index()/the fused step gather unrelated rows
            slots = self.table.key_index.lookup(self.vocab.keys)
            self._slot_of_vocab = jnp.asarray(slots, jnp.int32)
        return n

    def embedding(self, key: int) -> Optional[np.ndarray]:
        """Input-side (v) vector for an external key, or None."""
        if key not in self.table.key_index:
            return None
        slot = self.table.key_index.slot(key)
        n_hot = self.table.n_hot
        if slot < n_hot:            # replicated hot head (hybrid)
            from swiftmpi_tpu.parameter.sparse_table import hot_name
            row = self.table.state[hot_name("v")][slot]
        else:
            row = self.table.state["v"][slot - n_hot]  # one-row transfer
        return np.asarray(row)[:self.len_vec]

    def serving_publisher(self):
        """The model's :class:`~swiftmpi_tpu.serve.snapshot
        .SnapshotPublisher` — armed on first call (or by ``train()``
        when ``[serve] every > 0``).  Attach
        :class:`~swiftmpi_tpu.serve.reader.EmbeddingReader` instances to
        it from any number of query threads; ``train()`` publishes a
        versioned snapshot of the table (state + key→slot map) every
        ``[serve] every`` consumed steps."""
        if self.serve_publisher is None:
            from swiftmpi_tpu.serve.snapshot import SnapshotPublisher
            self.serve_publisher = SnapshotPublisher(
                every=max(self.serve_every, 1), depth=self.serve_depth)
        return self.serve_publisher

    def _serve_on_steps(self, n: int) -> None:
        """Trainer-thread publication hook: account ``n`` consumed steps
        and publish when the staleness bound is reached.  The key→slot
        view is captured HERE, on the trainer thread — a ``grow()`` can
        never be mid-flight, so readers always see a matched
        (state, key map) pair."""
        pub = self.serve_publisher
        if pub is None:
            return
        pub.on_steps(self.table, n=n, keys=lambda: self.vocab.keys,
                     slots=lambda: np.asarray(self._slot_of_vocab),
                     meta={"query_field": "v"})

    def _serve_publish(self) -> None:
        """Unconditional publish (end of train(): readers should see the
        final state regardless of where the every-K cadence landed)."""
        pub = self.serve_publisher
        if pub is None:
            return
        pub.publish(self.table, keys=lambda: self.vocab.keys,
                    slots=lambda: np.asarray(self._slot_of_vocab),
                    meta={"query_field": "v"})

    # -- adaptive control plane (control/; [control] section) --------------
    def _arm_control(self) -> None:
        """Construct the control plane for this model: the decayed
        id-frequency sketch (seeded from the build-time vocab counts so
        evaluation 0 reproduces the static calibration — no startup
        flap), the knob registry, and the controller.  Knob appliers
        own the safe-point machinery: re-partition via
        ``SparseTable.repartition`` plus the grow()-style cache fixups."""
        from swiftmpi_tpu.control import Controller, DecayedSketch, Knob
        st = self.control_settings
        keys = np.asarray(self.vocab.keys, np.uint64)
        self._control_key_order = np.argsort(keys, kind="stable")
        self._control_sorted_keys = keys[self._control_key_order]
        self._control_sketch = DecayedSketch(
            len(self.vocab), decay=st.decay,
            seed_counts=self.vocab.counts)
        self._control_recompiles = 0
        knobs = []
        if getattr(self.transfer, "name", "") == "hybrid":
            knobs.append(Knob(
                "hot_k",
                current=lambda: int(self.table.key_index.n_hot),
                propose=self._propose_hot_k,
                apply=self._apply_hot_k,
                describe=lambda p: {"n_hot": int(p.n_hot),
                                    "head_mass": p.head_mass}))
        if self.inner_steps > 1 and hasattr(self.transfer,
                                            "push_window"):
            knobs.append(Knob(
                "push_window",
                current=lambda: int(self.push_window_size),
                propose=self._propose_push_window,
                apply=self._apply_push_window))
            knobs.append(Knob(
                "wire_format",
                current=lambda: float(
                    self.transfer.window_expected_unique or 0.0),
                propose=self._propose_wire,
                apply=self._apply_wire))
        if (self.collective_mode != "psum"
                and getattr(self.transfer, "name", "") == "hybrid"
                and self.inner_steps > 1
                and hasattr(self.transfer, "push_window")):
            # collective crossover input: the hot-touch density the
            # sparse-allreduce pricing reads (transfer/plan.py
            # compile_hot_plan keys its cache on it, so an apply is a
            # reprice, not an invalidation protocol)
            knobs.append(Knob(
                "collective",
                current=lambda: float(
                    self.transfer.hot_touched_fraction or 0.0),
                propose=self._propose_collective,
                apply=self._apply_collective))
        self.controller = Controller(st, transfer=self.transfer,
                                     sketch=self._control_sketch,
                                     knobs=knobs)
        # wire tracer hot-key attribution: the sketch's decayed counts
        # replace the reservoir's touch estimates (obs/trace.py)
        tracer = obs.get_tracer()
        if tracer is not None:
            tracer.attach_sketch(self._control_sketch)

    def _control_on_steps(self, n: int) -> bool:
        """Trainer-thread control hook — called at the same safe points
        the serving plane publishes at (no dispatch in flight, table
        state current).  Returns True when an applied decision re-laid
        out the table or rebuilt the compiled step, i.e. the train
        loop must refresh its local state reference."""
        ctl = self.controller
        if ctl is None:
            return False
        self._control_dirty = False
        ctl.on_steps(n)
        return self._control_dirty

    def _control_mass(self, keys_arr, counts) -> float:
        """Sketch mass carried by a key set (keys must be vocab keys)."""
        keys_arr = np.asarray(keys_arr, np.uint64).ravel()
        if keys_arr.size == 0:
            return 0.0
        pos = np.searchsorted(self._control_sorted_keys, keys_arr)
        pos = np.minimum(pos, self._control_sorted_keys.size - 1)
        return float(counts[self._control_key_order[pos]].sum())

    def _rebuild_step(self) -> None:
        """Safe-point recompile: a knob change that moves rows or
        reshapes the window program invalidates every compiled step
        (capacity, n_hot and the window layout are baked in at trace
        time) — the ``grow()`` fixup contract, owned here for the
        control-plane appliers."""
        self._fused_cache = {}
        # control hooks never fire on the hogwild path; a stale step
        # cannot be reached, but drop it anyway for symmetry (the next
        # train() builds it).  A multi-process hogwild run trains in the
        # snapshot mode (train()), and gets that mode's programs
        hogwild = self.async_mode == "hogwild" and jax.process_count() == 1
        self._step = None if hogwild else self._make_step()
        self._control_recompiles += 1
        self._control_dirty = True

    def _propose_hot_k(self, counts, delta):
        """Re-run the hot/cold calibration on the decayed histogram.
        Win = token-mass points the re-derived hot set captures over
        the current one, under the CURRENT traffic distribution."""
        if counts is None:
            return None
        total = float(counts.sum())
        if total <= 0:
            return None
        from swiftmpi_tpu.control import Proposal
        from swiftmpi_tpu.parameter.key_index import HotColdPartition
        # x1024: from_counts quantizes to int64 — keep ~10 fractional
        # bits of the decayed histogram instead of truncating it
        part = HotColdPartition.from_counts(
            self.vocab.keys, counts * 1024.0, batch_rows=self.minibatch)
        cur = self.table.key_index.partition
        if cur is not None and part == cur:
            return None
        new_mass = self._control_mass(part.hot_keys, counts) / total
        cur_mass = (self._control_mass(cur.hot_keys, counts) / total
                    if cur is not None and cur.n_hot else 0.0)
        return Proposal(part, new_mass - cur_mass, {
            "old_n_hot": int(cur.n_hot) if cur is not None else 0,
            "new_n_hot": int(part.n_hot),
            "old_head_mass": cur_mass, "new_head_mass": new_mass,
            "sketch_observed": int(self._control_sketch.observed)})

    def _apply_hot_k(self, part, evidence) -> bool:
        """Re-partition at the safe point.  A shard without room for
        the demoted rows rejects the decision (CapacityError is raised
        before any mutation — the table is untouched)."""
        from swiftmpi_tpu.parameter.key_index import CapacityError
        try:
            plan = self.table.repartition(part)
        except CapacityError as e:
            evidence["error"] = str(e)
            return False
        evidence["moved_rows"] = int(plan.moved_rows)
        slots = self.table.key_index.lookup(self.vocab.keys)
        self._slot_of_vocab = jnp.asarray(slots, jnp.int32)
        self._rebuild_step()
        return True

    def _propose_push_window(self, counts, delta):
        """Retune the window width over {W/2, W, 2W} (capped at
        inner_steps — the staleness bound W-1 never exceeds one fused
        group).  Cost = expected unique rows on the wire per train
        step, E[U(w*B)]/w — row_bytes cancels out of the comparison."""
        if counts is None:
            return None
        from swiftmpi_tpu.cluster.hashfrag import expected_unique_rows
        from swiftmpi_tpu.control import Proposal
        W = self.push_window_size
        B = self.minibatch
        cands = sorted({max(1, W // 2), W,
                        min(2 * W, max(self.inner_steps, 1))})
        if len(cands) == 1:
            return None

        def cost(w):
            return expected_unique_rows(counts, w * B) / w

        cur_cost = cost(W)
        if cur_cost <= 0:
            return None
        best = min(cands, key=cost)
        if best == W:
            return None
        return Proposal(int(best), (cur_cost - cost(best)) / cur_cost, {
            "old_w": int(W), "new_w": int(best),
            "rows_per_step_old": cur_cost,
            "rows_per_step_new": cost(best)})

    def _apply_push_window(self, w, evidence) -> bool:
        w = int(w)
        self.push_window_size = w
        if hasattr(self.transfer, "window_expected_unique"):
            from swiftmpi_tpu.cluster.hashfrag import \
                expected_unique_rows
            self.transfer.window_expected_unique = (
                expected_unique_rows(self._control_sketch.counts,
                                     w * self.minibatch)
                if w > 1 else None)
        self._rebuild_step()
        return True

    def _propose_wire(self, counts, delta):
        """Refresh the per-window wire-format crossover input: the
        expected unique-row count under the DECAYED histogram.  Win =
        relative drift of E[U] since it was last baked in.  Evidence
        carries the priced format the crossover would pick under the old
        vs the new estimate (a representative one-field window family),
        so a decision log shows when a retune actually flips the baked
        format rather than just nudging the estimate."""
        if counts is None or self.push_window_size <= 1:
            return None
        old = getattr(self.transfer, "window_expected_unique", None)
        if old is None:
            return None
        from swiftmpi_tpu.cluster.hashfrag import expected_unique_rows
        from swiftmpi_tpu.control import Proposal
        from swiftmpi_tpu.parameter.key_index import window_wire_format
        new = expected_unique_rows(
            counts, self.push_window_size * self.minibatch)
        d = self.row_width
        row_bytes = 4 + 4 * d + 4          # i32 index + f32 row + counts
        qrb = 4 + (d + 4 if self.wire_quant == "int8" else 2 * d) + 4 \
            if self.wire_quant != "off" else None
        rows = self.push_window_size * self.minibatch

        def _fmt(eu):
            return window_wire_format(
                rows, self.table.capacity, row_bytes,
                dense_ratio=self.transfer.wire_dense_ratio("window"),
                expected_unique=eu, quant=self.wire_quant,
                quant_row_bytes=qrb,
                quant_guard=self.transfer.wire_quant_guard,
                sketch=bool(getattr(self.transfer, "wire_sketch", False)))

        return Proposal(float(new), abs(new - old) / max(float(old), 1.0),
                        {"old_expected_unique": float(old),
                         "new_expected_unique": float(new),
                         "old_format": _fmt(float(old)),
                         "new_format": _fmt(float(new))})

    def _propose_collective(self, counts, delta):
        """Refresh the hot-touch density the collective crossover
        prices by (key_index.price_hot_collectives): recompute the
        expected touched fraction of the hot head under the DECAYED
        histogram — the same saturation model the build seeds from the
        static vocab counts.  Win = relative drift of the fraction.
        Evidence carries the collective the crossover would pick under
        the old vs new density (a representative one-field family, like
        _propose_wire's), so the decision log shows when a retune flips
        the baked collective rather than just nudging the signal."""
        if counts is None or self.push_window_size <= 1:
            return None
        n_hot = int(self.table.key_index.n_hot)
        if n_hot <= 0:
            return None
        old = getattr(self.transfer, "hot_touched_fraction", None)
        if old is None:
            return None
        from swiftmpi_tpu.control import Proposal
        from swiftmpi_tpu.parameter.key_index import price_hot_collectives
        c = np.asarray(counts, np.float64).ravel()
        total = c.sum()
        if total <= 0:
            return None
        head = np.sort(c)[::-1][:n_hot] / total
        draws = self.push_window_size * self.minibatch
        new = min(float(np.sum(-np.expm1(
            draws * np.log1p(-np.minimum(head, 1.0))))) / n_hot, 1.0)

        def _pick(frac):
            decision, _ = price_hot_collectives(
                n_hot, 4 * self.len_vec + 4, frac,
                sparse_ar_ratio=self.transfer.sparse_ar_ratio)
            return decision

        return Proposal(float(new),
                        abs(new - old) / max(float(old), 1e-6),
                        {"old_touched_fraction": float(old),
                         "new_touched_fraction": float(new),
                         "old_collective": _pick(float(old)),
                         "new_collective": _pick(float(new))})

    def _apply_collective(self, frac, evidence) -> bool:
        self.transfer.hot_touched_fraction = float(frac)
        # the collective is baked into the compiled reconcile at trace
        # time; the hot plan cache keys on the density signal, so this
        # write IS the reprice — recompile so it takes effect at this
        # safe point
        self._rebuild_step()
        return True

    def _apply_wire(self, eu, evidence) -> bool:
        self.transfer.window_expected_unique = float(eu)
        # the wire-format decision is host-static, baked at trace time
        # (the TrafficPlan compiled in transfer/api.py's window
        # interpreter; the plan cache keys on expected_unique, so this
        # write invalidates the cached plan) — recompile so the new
        # crossover takes effect at this safe point
        self._rebuild_step()
        return True

    # -- numerics health plane (obs/numerics.py; [obs] numerics) -----------
    def _arm_numerics(self, tel_rec) -> None:
        """Arm the numerics health plane for this train() call: build
        the collector + detector once, restore checkpointed baselines,
        install the registry sampler on the recorder, point the
        transfer-wide quantization-error tap at the collector, and
        register the Controller demote hook.  A first-time arm drops
        any step compiled before it — the traced bundle is baked in at
        trace time, so train() compiles AFTER this runs."""
        from swiftmpi_tpu.obs import numerics as obs_numerics
        from swiftmpi_tpu.transfer import api as transfer_api
        if self._numerics is None:
            det = obs_numerics.detector_from_config(self.config)
            if self._numerics_restore is not None:
                det.load_state_bytes(self._numerics_restore)
                self._numerics_restore = None
            self._numerics = obs_numerics.NumericsCollector(detector=det)
            self._step = None
            self._fused_cache = {}
            if self.controller is not None:
                self.controller.attach_numerics(det, self._numerics_demote)
        transfer_api.set_numerics_tap(self._numerics.quant_tap)
        if id(tel_rec) != self._numerics_rec_id:
            # one sampler per recorder: train() may be called repeatedly
            # against the same long-lived recorder (bench harness)
            tel_rec.add_sampler(self._numerics.sampler)
            self._numerics_rec_id = id(tel_rec)

    def _poison_row(self, state: dict) -> dict:
        """``nan`` fault consumption (testing/faults.py): overwrite one
        live parameter row with NaN — the injectable stand-in for a
        numerics blow-up the health plane must catch.  Returns the new
        state (also installed on the table)."""
        f = self.access.grad_fields[0]
        state = dict(state)
        state[f] = jnp.asarray(state[f]).at[0].set(jnp.nan)
        self.table.state = state
        log.warning("fault injection: poisoned %s row 0 with NaN", f)
        return state

    def _numerics_demote(self, anomaly: dict) -> Optional[str]:
        """Controller-applied numerics action: sustained EF-residual
        runaway drops ``wire_quant`` to lossless at the control plane's
        safe point — the quantizer is banking error faster than the
        residual drains, and kept on int8 the model walks away from the
        lossless trajectory.  ``pull_quant`` is demoted on the same
        trigger (the read-side quantizer feeds the same forward pass;
        OPERATIONS.md documents this as the pull plane's escape hatch —
        the lossless pull cache stays armed).  Returns the previous
        setting (for the decision event) or None when already
        lossless."""
        old_w, old_p = self.wire_quant, self.pull_quant
        if old_w == "off" and old_p == "off":
            return None
        log.warning(
            "numerics: sustained EF residual runaway on %s — demoting "
            "wire_quant %s -> off, pull_quant %s -> off",
            anomaly.get("series"), old_w, old_p)
        self.wire_quant = "off"
        self.pull_quant = "off"
        if hasattr(self.transfer, "wire_quant"):
            self.transfer.wire_quant = "off"
        self.transfer.pull_quant = "off"
        self._rebuild_step()
        return old_w if old_w != "off" else f"pull:{old_p}"

    def embedding_index(self, field: str = "v"):
        """Cosine-similarity index over the LIVE table (no dump round
        trip): ``model.embedding_index().neighbors(key)`` /
        ``.analogy(a, b, c)``.  Snapshot semantics — build after
        training (or rebuild to see newer updates).  The reference has
        no in-process query path at all (dump + external scripts)."""
        from swiftmpi_tpu.models.embedding import EmbeddingIndex

        if self.vocab is None:
            # load() restores table rows but not a vocab; a dump-only
            # workflow should index the dump file directly
            raise RuntimeError(
                "no vocab; build()/build_from_vocab() first (after a "
                "bare load(), use EmbeddingIndex.from_text on the dump)")
        slots = np.asarray(self._slot_of_vocab)
        vecs = self.table.unified_rows_host(field)[slots]
        return EmbeddingIndex(self.vocab.keys, vecs)
