"""GloVe (global word-vector factorization) on the TPU parameter server.

Beyond the reference's app set (SURVEY.md §2.5 lists LR, word2vec,
sent2vec) — included to show the framework's worker API generalizes past
its three ported apps: GloVe's original trainer is **server-side AdaGrad
over a sharded sparse table**, exactly the reference's parameter-server
contract (accessmethod.h plugins + pull/push), so the whole model is an
access-method schema plus one fused jitted step.

Math (Pennington et al. 2014): for each co-occurrence count x_ij,

    J_ij = w_i . wt_j + b_i + bt_j - log(x_ij)
    loss = f(x_ij) * J_ij^2,   f(x) = min((x / x_max)^alpha, 1)

with symmetric-window counts weighted 1/distance, trained by AdaGrad on
(w, b) of the focus word and (wt, bt) of the context word.  The final
embedding is the standard w + wt sum.

TPU-first shape: the co-occurrence set is built ONCE host-side as COO
arrays, then every epoch is a shuffled `lax.scan` over fused minibatch
steps — two row gathers, elementwise math, two mean-normalized pushes
through the transfer layer (the same path word2vec's h/v families
take).  No per-pair host work, no dynamic shapes.

Config section ``[glove]``: ``len_vec`` (default 100), ``window`` (10),
``x_max`` (100), ``alpha`` (0.75), ``learning_rate`` (0.05),
``minibatch`` (4096), plus ``[worker] inner_steps`` like the other
models.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from swiftmpi_tpu.cluster.cluster import Cluster
from swiftmpi_tpu.data.text import Vocab, build_vocab
from swiftmpi_tpu.io.checkpoint import dump_table_text
from swiftmpi_tpu.parameter.access import (AdaGradAccess, AdaGradRule,
                                           FieldSpec, row_field,
                                           vec_rand_init, zeros_init)
from swiftmpi_tpu.utils.config import ConfigParser, global_config
from swiftmpi_tpu.utils.logger import get_logger

log = get_logger(__name__)


def glove_access(learning_rate: float, len_vec: int) -> AdaGradAccess:
    """One table keyed by word: focus (w, b) and context (wt, bt)
    families with per-element AdaGrad sums — the optimizer GloVe
    shipped with, already the framework's native access method."""
    return AdaGradAccess(
        learning_rate,
        rules=(AdaGradRule("w", "w2sum", "w"),
               AdaGradRule("wt", "wt2sum", "wt"),
               AdaGradRule("b", "b2sum", "b"),
               AdaGradRule("bt", "bt2sum", "bt")),
        fields={"w": row_field(len_vec, vec_rand_init),
                "wt": row_field(len_vec, vec_rand_init),
                "b": FieldSpec(1, zeros_init),
                "bt": FieldSpec(1, zeros_init),
                "w2sum": row_field(len_vec),
                "wt2sum": row_field(len_vec),
                "b2sum": FieldSpec(1, zeros_init),
                "bt2sum": FieldSpec(1, zeros_init)},
        pull_fields=("w", "wt", "b", "bt"),
    )


def cooccurrence(sentences, vocab: Vocab, window: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Symmetric-window co-occurrence counts, weight ``1/distance``
    (the GloVe paper's decreasing weighting).  Returns COO arrays
    (focus_idx, ctx_idx, weight) over VOCAB indices, deduplicated.

    Vectorized per offset: for distance k every in-sentence token pair
    (t, t+k) contributes 1/k to BOTH (i,j) and (j,i); pairs are folded
    by combined int64 key with ``np.unique`` — no per-pair python."""
    V = len(vocab.keys)
    idx_rows: List[np.ndarray] = []
    wts: List[np.ndarray] = []
    for sent in sentences:
        ids = [vocab.index_of(k) for k in sent]
        t = np.asarray([i for i in ids if i is not None], np.int64)
        if len(t) < 2:
            continue
        for k in range(1, min(window, len(t) - 1) + 1):
            a, b = t[:-k], t[k:]
            idx_rows.append(a * V + b)
            idx_rows.append(b * V + a)
            w = np.full(len(a), 1.0 / k, np.float32)
            wts.append(w)
            wts.append(w)
    if not idx_rows:
        return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32))
    combined = np.concatenate(idx_rows)
    weights = np.concatenate(wts)
    uniq, inv = np.unique(combined, return_inverse=True)
    x = np.zeros(len(uniq), np.float32)
    np.add.at(x, inv, weights)
    return ((uniq // V).astype(np.int32), (uniq % V).astype(np.int32), x)


class GloVe:
    def __init__(self, config: Optional[ConfigParser] = None,
                 cluster: Optional[Cluster] = None,
                 capacity_per_shard: Optional[int] = None, seed: int = 0):
        self.config = config if config is not None else global_config()
        g = self.config.get_or
        self.len_vec = g("glove", "len_vec", 100).to_int32()
        self.window = g("glove", "window", 10).to_int32()
        self.x_max = g("glove", "x_max", 100.0).to_float()
        self.alpha = g("glove", "alpha", 0.75).to_float()
        lr = g("glove", "learning_rate", 0.05).to_float()
        self.minibatch = g("glove", "minibatch", 4096).to_int32()
        self.inner_steps = g("worker", "inner_steps", 1).to_int32()
        # [worker] pipeline / dispatch_depth: same knobs as word2vec —
        # K > 0 stages+transfers groups on a producer thread
        # (io/pipeline.py); epoch permutations are still drawn on the
        # consumer thread in epoch order, so results are identical
        self.pipeline_depth = g("worker", "pipeline", 0).to_int32()
        self.dispatch_depth = g("worker", "dispatch_depth",
                                "auto").to_string()
        self.cluster = cluster or Cluster(self.config).initialize()
        self.access = glove_access(lr, self.len_vec)
        self.transfer = self.cluster.transfer
        self.seed = seed
        self._capacity_per_shard = capacity_per_shard
        self.table = None
        self.vocab: Optional[Vocab] = None
        self._slot_of_vocab = None
        self._coo = None
        self._step = None
        # per-train() observability: stall/device time split (+ the
        # pipeline depth the run actually used) — see utils.timers
        self.train_metrics: dict = {}
        # [obs] numerics (obs/numerics.py): off constructs and traces
        # nothing — same bit-identity contract as word2vec
        from swiftmpi_tpu.obs import numerics as obs_numerics
        self.numerics_on = obs_numerics.enabled(self.config)
        self._numerics = None
        self._numerics_rec_id: Optional[int] = None

    # -- build: vocab + co-occurrence + table ------------------------------
    def build(self, sentences) -> "GloVe":
        self.vocab = build_vocab(sentences)
        V = len(self.vocab.keys)
        cap = self._capacity_per_shard or max(
            64, int(V * 1.3) // self.cluster.n_servers + 1)
        self.table = self.cluster.create_table(
            "glove", self.access, cap, seed=self.seed)
        slots = self.table.key_index.lookup(self.vocab.keys)
        self._slot_of_vocab = jnp.asarray(slots, jnp.int32)
        fi, ci, x = cooccurrence(sentences, self.vocab, self.window)
        self._coo = (fi, ci, x)
        log.info("glove: vocab %d, %d co-occurrence cells (window %d)",
                 V, len(x), self.window)
        return self

    # -- fused step --------------------------------------------------------
    def _build_step(self):
        # fx/logx arrive precomputed from train() — the weighting
        # function itself never enters the jitted step
        access, transfer = self.access, self.transfer
        from swiftmpi_tpu.obs import numerics as obs_numerics
        num = self._numerics
        n_hot = self.table.n_hot if num is not None else 0

        def one(state, fs, cs, logx, fx):
            rows_f = transfer.pull(state, fs, access, fields=("w", "b"))
            rows_c = transfer.pull(state, cs, access, fields=("wt", "bt"))
            w, b = rows_f["w"], rows_f["b"][:, 0]
            wt, bt = rows_c["wt"], rows_c["bt"][:, 0]
            J = jnp.sum(w * wt, axis=1) + b + bt - logx
            g = fx * J                                   # dJ/d(dot)
            loss = jnp.sum(fx * J * J)
            # AdaGradAccess ADDS lr*g (the reference's ascent
            # convention, lr.cpp:68-75) — push the NEGATIVE gradient
            gw = (-g)[:, None] * wt
            gwt = (-g)[:, None] * w
            gb = (-g)[:, None]
            stats = None
            if num is not None:
                s1, h1, n1 = obs_numerics.push_stats(
                    fs, {"w": gw, "b": gb}, n_hot)
                s2, h2, n2 = obs_numerics.push_stats(
                    cs, {"wt": gwt, "bt": gb}, n_hot)
                stats = (s1 + s2, h1 + h2, n1 + n2)
            state = transfer.push(state, fs, {"w": gw, "b": gb},
                                  access, mean=True)
            state = transfer.push(state, cs, {"wt": gwt, "bt": gb},
                                  access, mean=True)
            return state, loss, stats

        def multi(state, fs, cs, logx, fx):
            if num is None:
                def body(st, xs):
                    st, loss, _ = one(st, *xs)
                    return st, loss
                state, losses = jax.lax.scan(body, state,
                                             (fs, cs, logx, fx))
                return state, losses.sum()
            state0 = state

            def body(st, xs):
                st, loss, stats = one(st, *xs)
                return st, (loss, stats)
            state, (losses, stats) = jax.lax.scan(body, state,
                                                  (fs, cs, logx, fx))
            obs_numerics.stage_step(
                num, state0, state, tuple(s.sum() for s in stats),
                losses.sum(), jnp.float32(fs.shape[0] * fs.shape[1]),
                ("w", "wt", "b", "bt"))
            return state, losses.sum()

        from swiftmpi_tpu import obs
        return obs.costs.track("glove_step",
                               jax.jit(multi, donate_argnums=(0,)),
                               steps_per_call=max(1, self.inner_steps))

    # -- minibatch staging -------------------------------------------------
    def stage_host(self, sel: np.ndarray, inner: int, B: int):
        """COO selection -> host ``(fs, cs, logx, fx)`` stacks of shape
        (inner, B): the ONE definition of slot mapping and the
        f(x) = min((x/x_max)^alpha, 1) weighting, shared by train() and
        the benchmark cell so a weighting change can't silently fork.
        Pure numpy — this is what the input pipeline's producer thread
        runs off the critical path."""
        fi, ci, x = self._coo
        sov = np.asarray(self._slot_of_vocab)
        sel = np.resize(sel, inner * B)
        xs = x[sel]
        return (sov[fi[sel]].reshape(inner, B),
                sov[ci[sel]].reshape(inner, B),
                np.log(xs).reshape(inner, B),
                np.minimum((xs / self.x_max) ** self.alpha,
                           1.0).astype(np.float32).reshape(inner, B))

    def stage(self, sel: np.ndarray, inner: int, B: int):
        """Device-side ``stage_host``."""
        return tuple(jnp.asarray(f)
                     for f in self.stage_host(sel, inner, B))

    # -- training ----------------------------------------------------------
    def train(self, sentences=None, niters: int = 1) -> List[float]:
        if self.table is None:
            if sentences is None:
                raise RuntimeError("build() first or pass sentences")
            self.build(sentences)
        n = len(self._coo[2])
        if n == 0:
            raise RuntimeError("empty co-occurrence set")
        B = min(self.minibatch, n)
        inner = max(1, self.inner_steps)
        rng = np.random.default_rng(self.seed)
        state = self.table.state
        losses = []
        from swiftmpi_tpu.utils.timers import Throughput
        meter = Throughput()
        from swiftmpi_tpu import obs
        tel_rec = obs.get_recorder()
        owns_rec = tel_rec is None
        if owns_rec:
            tel_rec = obs.configure(self.config, run="glove")
        if tel_rec is not None:
            def _tel_sample(reg, _m=meter):
                reg.counter("train/host_stall_ms_total").set_total(
                    _m.host_stall_ms())
            tel_rec.add_sampler(_tel_sample)
        if self.numerics_on and tel_rec is not None:
            self._arm_numerics(tel_rec)
        # compile AFTER arming: _build_step closes over self._numerics
        # at trace time (a first-time arm drops any pre-arm step)
        if self._step is None:
            self._step = self._build_step()
        transfer_fn = None
        if self.pipeline_depth > 0:
            from swiftmpi_tpu.io.pipeline import device_put_transfer
            sharding = jax.sharding.NamedSharding(
                self.cluster.mesh, jax.sharding.PartitionSpec())
            transfer_fn = device_put_transfer(sharding)

        def staged_groups(order):
            # the epoch permutation was already drawn (consumer thread,
            # epoch order) — from here on the staging is pure numpy, so
            # it can run ahead on the producer thread
            for gstart in range(0, len(order), B * inner):
                yield self.stage_host(order[gstart:gstart + B * inner],
                                      inner, B)

        for it in range(niters):
            order = rng.permutation(n)
            # pad the tail by CYCLING the permutation (static shapes,
            # via stage_host()'s np.resize — holds even when one fused
            # group exceeds n); repeats are extra stochastic samples
            # of real cells, and per-slot mean normalization keeps
            # their scale right
            n_groups = -(-n // (B * inner))
            order = np.resize(order, n_groups * B * inner)
            total = 0.0
            groups = staged_groups(order)
            pipe = None
            if self.pipeline_depth > 0:
                from swiftmpi_tpu.io.pipeline import PrefetchIterator
                pipe = PrefetchIterator(groups,
                                        depth=self.pipeline_depth,
                                        transfer=transfer_fn)
                groups = pipe
            try:
                groups = iter(groups)
                while True:
                    with meter.stalling():
                        fields = next(groups, None)
                    if fields is None:
                        break
                    with obs.span("dispatch"):
                        state, loss = self._step(
                            state, *(jnp.asarray(f) if not isinstance(
                                f, jax.Array) else f for f in fields))
                    # the step donates the state buffers: reassign NOW,
                    # not after the loop, or an exception mid-epoch
                    # (staging error, KeyboardInterrupt) leaves
                    # self.table.state pointing at donated/deleted
                    # device buffers and a previously valid model can
                    # no longer save() (round-3 advisor)
                    self.table.state = state
                    total += float(loss)
                    meter.record(B * inner)
                    obs.record_step(inner)
            finally:
                if pipe is not None:
                    pipe.close()
            mean_loss = total / len(order)
            losses.append(mean_loss)
            log.info("glove iter %d: %d cells  loss %.6f", it, n, mean_loss)
        self.train_metrics = {
            "host_stall_ms": meter.host_stall_ms(),
            "stall_ms_per_step": meter.stall_ms_per_step(),
            "pipeline_depth": self.pipeline_depth}
        if self._numerics is not None:
            from swiftmpi_tpu.transfer import api as transfer_api
            self._numerics.sync()
            transfer_api.clear_numerics_tap()
            det = self._numerics.detector
            self.train_metrics["numerics"] = {
                "bundles": self._numerics.bundles,
                "anomalies": det.anomalies_emitted if det else 0}
        if owns_rec and tel_rec is not None:
            tel_rec.close()
            obs.uninstall_recorder()
        return losses

    def _arm_numerics(self, tel_rec) -> None:
        """Arm the numerics plane (observe-only here: GloVe has no
        control plane, so anomalies are telemetry events, never knob
        actions).  Mirrors Word2Vec._arm_numerics minus the controller
        and checkpoint-carry pieces."""
        from swiftmpi_tpu.obs import numerics as obs_numerics
        from swiftmpi_tpu.transfer import api as transfer_api
        if self._numerics is None:
            self._numerics = obs_numerics.NumericsCollector(
                detector=obs_numerics.detector_from_config(self.config))
            self._step = None
        transfer_api.set_numerics_tap(self._numerics.quant_tap)
        if id(tel_rec) != self._numerics_rec_id:
            tel_rec.add_sampler(self._numerics.sampler)
            self._numerics_rec_id = id(tel_rec)

    # -- outputs -----------------------------------------------------------
    def _vectors(self) -> np.ndarray:
        """The exported embedding: standard w + wt sum, vocab order —
        ONE definition shared by the live index and the dump."""
        if self.vocab is None:
            raise RuntimeError("build() first")
        slots = np.asarray(self._slot_of_vocab)
        return (self.table.unified_rows_host("w")[slots]
                + self.table.unified_rows_host("wt")[slots])

    def embedding_index(self):
        """Cosine index over the standard w + wt embedding sum."""
        from swiftmpi_tpu.models.embedding import EmbeddingIndex

        return EmbeddingIndex(self.vocab.keys, self._vectors())

    def save(self, path: str) -> int:
        """``key TAB (w + wt)-vector`` — the standard GloVe export, in
        the single-vector dump layout ``w2v_eval`` indexes directly."""
        vecs = self._vectors()
        n = 0
        with open(path, "w") as f:
            for key, vec in zip(self.vocab.keys, vecs):
                f.write(f"{int(key)}\t"
                        + " ".join(repr(float(v)) for v in vec) + "\n")
                n += 1
        return n

    def save_full(self, path: str) -> int:
        """All fields (both families + AdaGrad sums) in the reference
        checkpoint text format."""
        return dump_table_text(self.table, path,
                               fields=("w", "wt", "b", "bt"))
