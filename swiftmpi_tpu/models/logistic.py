"""Sparse logistic regression on the TPU parameter server.

Re-design of the reference LR app (`/root/reference/src/apps/logistic/
lr.cpp`), same capability and math, TPU-shaped execution:

* reference: per minibatch, multithreaded per-line ``learn_instance``
  (sigmoid dot + per-key grad accumulation, lr.cpp:355-375) around a
  pull/push RPC pair (lr.cpp:213-236).
* here: the whole minibatch is one jitted SPMD step — padded ``(B, F)``
  feature matrices, masked sigmoid-dot, per-key mean-normalized gradient
  (the reference's ``grad/count`` at serialization, lr.cpp:32-38) computed
  in-step, then a transfer push applying server-side AdaGrad
  (lr.cpp:68-75).

Math parity: predict = σ(Σ w_f·x_f); err = target − predict (gradient
*ascent* on log-likelihood); per-iteration training error = mean err²
(lr.cpp:358-375); AdaGrad with fudge 1e-6; weights initialized U(0,1) by
``gen_float`` (lr.cpp:48-50) — here the same distribution via jax.random.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from swiftmpi_tpu.cluster.cluster import Cluster
from swiftmpi_tpu.data.libsvm import (CSRData, LibSVMBatch, iter_minibatches,
                                      load_data, load_file)  # noqa: F401
from swiftmpi_tpu.io.checkpoint import (dump_table_text, load_table_text)
from swiftmpi_tpu.parameter import lr_access
from swiftmpi_tpu.parameter.key_index import CapacityError
from swiftmpi_tpu.utils.config import ConfigParser, global_config
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.pipeline import DispatchWindow

log = get_logger(__name__)


def _max_feats(data) -> int:
    if isinstance(data, CSRData):
        return data.max_feats
    return max(len(f) for _, f in data)


def lr_formatter(row: Dict[str, np.ndarray]) -> str:
    """Reference LRParam operator<<: just the weight (lr.cpp:24-27)."""
    return repr(float(row["val"][0]))


def lr_parser(text: str) -> Dict[str, np.ndarray]:
    return {"val": np.array([float(text.split()[0])], np.float32)}


class LogisticRegression:
    def __init__(self, config: Optional[ConfigParser] = None,
                 cluster: Optional[Cluster] = None,
                 capacity_per_shard: int = 1 << 16, seed: int = 0):
        self.config = config if config is not None else global_config()
        self.minibatch = (self.config.get("worker", "minibatch").to_int32()
                          if self.config.has("worker", "minibatch") else 200)
        lr = (self.config.get("server", "initial_learning_rate").to_float()
              if self.config.has("server", "initial_learning_rate") else 0.05)
        self.cluster = cluster or Cluster(self.config).initialize()
        self.access = lr_access(lr)
        self.table = self.cluster.create_table(
            "lr", self.access, capacity_per_shard, seed=seed)
        self.transfer = self.cluster.transfer
        # [worker] inner_steps: fuse N minibatches per dispatch via
        # lax.scan, as in word2vec — an a9a-scale step is small next to
        # the per-dispatch overhead (not measured on this stack)
        self.inner_steps = (
            self.config.get("worker", "inner_steps").to_int32()
            if self.config.has("worker", "inner_steps") else 1)
        # [worker] dense_features: auto|0|1 — capacity-dense rendering
        # for small feature spaces (see _dense_core)
        self.dense_features = (
            self.config.get("worker", "dense_features").to_string()
            if self.config.has("worker", "dense_features") else "auto")
        # [worker] scan_unroll: lax.scan unroll factor for the fused
        # multi-batch step — at a9a scale each iteration is microseconds
        # of MXU work, so per-iteration loop overhead can dominate;
        # unrolling lets XLA pipeline iterations (A/B'd on chip)
        self.scan_unroll = (
            self.config.get("worker", "scan_unroll").to_int32()
            if self.config.has("worker", "scan_unroll") else 1)
        self._step = None
        self._multi = None
        self._dense_step = None
        self._dense_multi = None

    # -- fused minibatch step ---------------------------------------------
    def _step_core(self, state, slots, vals, mask, targets):
        access = self.access
        transfer = self.transfer
        B, F = slots.shape
        flat = jnp.where(mask, slots, -1).reshape(-1)
        rows = transfer.pull(state, flat, access)["val"]
        w = rows.reshape(B, F)
        logits = jnp.sum(w * vals * mask, axis=1)
        predict = jax.nn.sigmoid(logits)
        row_valid = mask.any(axis=1)
        err = jnp.where(row_valid, targets - predict, 0.0)
        # mean=True: the reference's grad.val/grad.count normalization at
        # push serialization (lr.cpp:32-38), folded into the transfer's
        # dedup pass
        contrib = (err[:, None] * vals * mask).reshape(-1)
        new_state = transfer.push(
            state, flat, {"val": contrib[:, None]}, access, mean=True)
        loss = jnp.sum(err * err) / jnp.maximum(row_valid.sum(), 1)
        return new_state, loss, row_valid.sum()

    def _build_step(self):
        from swiftmpi_tpu import obs
        return obs.costs.track("lr_step", jax.jit(self._step_core))

    def _build_scan(self, core):
        """Scan a fused step over a stack of minibatches in ONE dispatch.

        The reference amortizes per-batch overhead with 13 worker threads
        per rank (lr.cpp:225); on TPU the equivalent lever is fusing the
        per-batch host->device round-trip away — the a9a-scale step
        compute is small next to the per-dispatch overhead (not
        measured on this stack).
        Inputs carry a leading ``n_batches`` axis; returns per-batch
        losses/counts so the training-error log stays per-minibatch."""

        unroll = max(1, self.scan_unroll)

        @jax.jit
        def multi(state, *cols):
            def body(state, xs):
                state, loss, n = core(state, *xs)
                return state, (loss, n)
            state, (losses, ns) = jax.lax.scan(body, state, cols,
                                               unroll=unroll)
            return state, losses, ns

        return multi

    def _build_multi_step(self):
        from swiftmpi_tpu import obs
        return obs.costs.track("lr_multi",
                               self._build_scan(self._step_core))

    # -- dense-features rendering -----------------------------------------
    # At a9a scale (123 features, capacity ~160) the padded-sparse step
    # is transaction-bound: B*F scalar weight gathers + a scatter push,
    # each ~10ns on chip regardless of width, cap the step far below
    # both the MXU and the CPU baseline (round-2 live window: 0.06x
    # CPU).  When the whole weight table is small, the TPU-first shape
    # is capacity-DENSE: densify each minibatch host-side once and the
    # step becomes two skinny MXU matmuls (X @ w, X^T @ err) plus a
    # dense AdaGrad apply — identical math (same per-key contribution
    # and count multiset, so the mean normalization and update rule
    # match the sparse push bit-for-bit modulo float summation order),
    # zero per-row transactions.  The sparse rendering remains the
    # general path for url/kdd-scale feature spaces.

    DENSE_CAP_LIMIT = 2048

    def dense_enabled(self) -> bool:
        mode = self.dense_features.lower()
        if mode in ("0", "off", "false"):
            return False
        if mode in ("1", "on", "true"):
            return True
        # auto: an MXU play — on CPU the densified batches move ~5x the
        # bytes of the padded-sparse layout and measure ~7x slower than
        # the sparse step, so auto only flips when THIS model's devices
        # are TPUs (not jax.devices()[0]: a process can expose both, and
        # a CPU-pinned run must not inherit the TPU verdict)
        dev = self.cluster.mesh.devices.flat[0]
        return (dev.platform == "tpu"
                and self.table.capacity <= self.DENSE_CAP_LIMIT)

    def _densify(self, slots, vals, mask, targets):
        """(B, F) padded-sparse batch -> capacity-dense ``(X, cnt, t, v)``:
        ``X[b, slot] += val`` and ``cnt[slot] += 1`` per valid
        (row, feature) occurrence — the same contribution and count
        multiset the sparse push sees (duplicate features in one row
        accumulate in both, as in the reference's per-key grad/count)."""
        cap = self.table.capacity
        B, F = slots.shape
        X = np.zeros((B, cap), np.float32)
        cnt = np.zeros((cap,), np.float32)
        m = np.asarray(mask, bool)
        rows = np.broadcast_to(np.arange(B)[:, None], (B, F))
        np.add.at(X, (rows[m], np.asarray(slots)[m]),
                  np.asarray(vals, np.float32)[m])
        # only the per-slot total ever feeds the mean normalization, so
        # ship the (cap,) reduction, not a (B, cap) presence matrix
        np.add.at(cnt, np.asarray(slots)[m], 1.0)
        return (X, cnt, np.asarray(targets, np.float32), m.any(axis=1))

    def _dense_core(self, state, X, cnt, targets, valid):
        access = self.access
        w = state["val"][:, 0].astype(jnp.float32)        # (cap,)
        predict = jax.nn.sigmoid(X @ w)
        err = jnp.where(valid, targets - predict, 0.0)
        # err @ X, not X.T @ err: the same contraction, but the spelled
        # transpose materializes a (cap, B) shuffle that measured ~3x
        # the whole remaining step on both backends
        grad = err @ X                                    # (cap,) MXU
        mean_grad = grad / jnp.maximum(cnt, 1.0)
        new_fields = access.apply_push(state,
                                       {"val": mean_grad[:, None]})
        state = {**state, **new_fields}
        n = valid.sum()
        loss = jnp.sum(err * err) / jnp.maximum(n, 1)
        return state, loss, n

    def _build_dense_step(self):
        from swiftmpi_tpu import obs
        return obs.costs.track("lr_dense_step",
                               jax.jit(self._dense_core))

    def _build_dense_multi(self):
        from swiftmpi_tpu import obs
        return obs.costs.track("lr_dense_multi",
                               self._build_scan(self._dense_core))

    # -- training (lr.cpp:157-240) ----------------------------------------
    def train(self, data, niters: int = 1,
              max_feats: Optional[int] = None) -> List[float]:
        """``data``: path to a libSVM file, a pre-parsed instance list, or
        ``CSRData`` (native parser output).  Returns per-iteration mean
        training error (reference logs ``error: total/nrecords`` per iter,
        lr.cpp:231)."""
        if isinstance(data, str):
            data = load_data(data)
        if self._step is None:
            self._step = self._build_step()
        inner = max(1, self.inner_steps)
        if inner > 1 and self._multi is None:
            self._multi = self._build_multi_step()
        F = max_feats or _max_feats(data)
        losses = []
        state = self.table.state
        # deferred per-batch loss scalars: fetched once per epoch (a
        # float() per batch is a blocking device round trip); the
        # DispatchWindow keeps the async pipeline bounded on the
        # emulated multi-device CPU mesh (see utils/pipeline.py for the
        # rendezvous-starvation failure mode it prevents)
        window = DispatchWindow()
        pending = []
        group = []

        def queue(loss, n):
            pending.append((loss, n))
            window.push(loss)

        def flush_group():
            nonlocal state
            if not group:
                return
            entries = group
            if self.dense_enabled():
                entries = [self._densify(*e) for e in entries]
                if self._dense_step is None:
                    self._dense_step = self._build_dense_step()
                    self._dense_multi = self._build_dense_multi()
                one, many = self._dense_step, self._dense_multi
            else:
                one, many = self._step, self._multi
            if len(entries) == inner and inner > 1:
                stacked = tuple(
                    jnp.asarray(np.stack(col)) for col in zip(*entries))
                state, ls, ns = many(state, *stacked)
                queue(ls, ns)
            else:
                # tail (or pre-grow flush) smaller than a full group:
                # per-batch dispatch avoids a recompile per distinct size
                for cols in entries:
                    state, loss, n = one(
                        state, *(jnp.asarray(c) for c in cols))
                    queue(loss, n)
            group.clear()

        for it in range(niters):
            total, count = 0.0, 0
            for batch in iter_minibatches(data, self.minibatch, F):
                keys = np.where(batch.mask, batch.feat_ids, 0)
                while True:
                    try:
                        slots = self.table.key_index.lookup(keys)
                        break
                    except CapacityError:
                        # unlike the reference's self-growing
                        # dense_hash_map, dense HBM arrays grow by explicit
                        # re-layout; the jitted step bakes in capacity, so
                        # rebuild it (loop: one batch may need >1 doubling).
                        # Queued batches hold OLD-layout slots — flush them
                        # through the old step first.
                        flush_group()
                        self.table.state = state   # sync the live buffers
                        self.table.grow()
                        log.info("table grown to %d rows",
                                 self.table.capacity)
                        self._step = self._build_step()
                        self._multi = (self._build_multi_step()
                                       if inner > 1 else None)
                        # dense programs bake in the old capacity too;
                        # rebuilt lazily at next flush (growth may also
                        # have pushed capacity past the dense limit)
                        self._dense_step = None
                        self._dense_multi = None
                        state = self.table.state
                group.append((slots, batch.feat_vals, batch.mask,
                              batch.targets))
                if len(group) == inner:
                    flush_group()
            flush_group()
            for loss, n in pending:
                loss, n = np.asarray(loss), np.asarray(n)
                # scanned groups return per-batch vectors
                total += float((loss * n).sum())
                count += int(n.sum())
            pending.clear()
            window.clear()
            mean_err = total / max(count, 1)
            losses.append(mean_err)
            log.info("iter %d: %d records  error: %.6f", it, count, mean_err)
        self.table.state = state
        return losses

    # -- prediction (lr.cpp:240-295) --------------------------------------
    def predict(self, data, max_feats: Optional[int] = None) -> np.ndarray:
        if isinstance(data, str):
            data = load_data(data)
        F = max_feats or _max_feats(data)
        scores = []
        for batch in iter_minibatches(data, self.minibatch, F):
            slots = self.table.key_index.lookup(
                np.where(batch.mask, batch.feat_ids, 0), create=False)
            slots = np.where(batch.mask, slots, -1)
            rows = self.transfer.pull(
                self.table.state, jnp.asarray(slots.reshape(-1)),
                self.access)["val"]
            w = np.asarray(rows).reshape(len(batch), F)
            logits = (w * batch.feat_vals * batch.mask).sum(axis=1)
            scores.append(1.0 / (1.0 + np.exp(-logits)))
        return np.concatenate(scores)[:len(data)]

    def error_rate(self, data) -> float:
        """Offline eval, the reference's tools/evaluate.py (26-line
        threshold-at-0.5 error rate)."""
        if isinstance(data, str):
            data = load_data(data)
        scores = self.predict(data)
        targets = (data.labels if isinstance(data, CSRData)
                   else np.array([y for y, _ in data]))
        return float(((scores > 0.5) != (targets > 0.5)).mean())

    # -- checkpoint (lr.cpp:297-300; server.h:49-77) -----------------------
    def save(self, path: str) -> int:
        return dump_table_text(self.table, path, fields=("val",))

    def load(self, path: str) -> int:
        n = load_table_text(self.table, path, fields=("val",))
        # loading may have grown the table; the jitted steps bake in the
        # old capacity (push scatter bounds), so force a rebuild on next
        # train()
        self._step = None
        self._multi = None
        self._dense_step = None
        self._dense_multi = None
        return n
