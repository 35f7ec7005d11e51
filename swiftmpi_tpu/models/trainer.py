"""Transformer training loop: optimizer, schedule, sharding, checkpoints.

The reference has no dense-model trainer at all (its optimizer lives
server-side as the AdaGrad push rule, accessmethod.h) — this is the
framework's training infrastructure for the transformer family, composed
the idiomatic TPU way:

* optimizer = optax (adamw/sgd + warmup-cosine), state sharded like the
  params so dp/tp carry over to the optimizer for free;
* one jitted, donated ``train_step``: loss, grads, update — GSPMD inserts
  every collective from the shardings alone;
* ``remat`` in TransformerConfig turns on per-block ``jax.checkpoint``
  (activation memory O(layers) -> O(1); recompute cost depends on
  ``remat_policy`` — "dots" saves matmul outputs and re-executes only
  elementwise ops and attention scores, "full" re-executes everything
  at ~1/3 extra FLOPs);
* checkpoints are flat npz (multihost-safe: collective gather, process-0
  writes — same policy as io/checkpoint.py), resume-exact including
  optimizer state and step counter.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from swiftmpi_tpu.cluster.bootstrap import host_array, is_writer
from swiftmpi_tpu.io.checkpoint import (atomic_savez, npz_path,
                                        prune_generations,
                                        rotate_before_write,
                                        verify_checkpoint)
from swiftmpi_tpu import obs
from swiftmpi_tpu.testing import faults
from swiftmpi_tpu.models.transformer import (TransformerConfig, init_params,
                                             is_frozen, lm_loss_and_stats,
                                             param_shardings)
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.pipeline import (DispatchWindow,
                                         resolve_dispatch_bound)
from swiftmpi_tpu.utils.timers import Throughput

log = get_logger(__name__)


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array          # replicated scalar int32

    def tree(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "step": self.step}


def make_optimizer(name: str = "adamw", learning_rate: float = 3e-4,
                   warmup_steps: int = 100, decay_steps: int = 10_000,
                   weight_decay: float = 0.01, grad_clip: float = 1.0,
                   b1: float = 0.9, b2: float = 0.999
                   ) -> optax.GradientTransformation:
    """Warmup-cosine schedule + global-norm clip around adamw/sgd."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(decay_steps, warmup_steps + 1))
    if name == "adamw":
        opt = optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay)
    elif name == "sgd":
        opt = optax.sgd(sched, momentum=0.9)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    return optax.chain(optax.clip_by_global_norm(grad_clip), opt)


def _expert_counters(stats) -> dict:
    """The expert layers' counters of a run, from the ``MoEStats`` its
    steps returned (summed over a step's expert layers): the share of the
    router's picks that landed on held experts, the share of the rows the
    expert loops walked that held such a pick (the rest are the last
    chunks' dead rows), the held experts' largest group over their mean
    (mean over layers and steps) and the held picks a step left uncomputed
    (0, always: nothing is dropped)."""
    if not stats:
        return {}
    total = {f: sum(float(getattr(s, f)) for s in stats)
             for f in stats[0]._fields}
    return {
        "held_pick_share": 100.0 * total["held"] / max(total["picks"], 1.0),
        "walk_fill_share": 100.0 * total["held"] / max(total["walked"], 1.0),
        "expert_load_max_over_mean":
            total["load_max_over_mean"] / max(total["layers"], 1.0),
        "dropped_picks_per_step": total["dropped"] / len(stats)}


def _loss_parts(parts, weight: float) -> dict:
    """What a run's steps with a multi-token-prediction module returned
    beside the loss (``lm_loss_and_stats``'s parts), as means over the
    steps: the two losses, the module's weighted share of their sum in
    percent, and the module's own expert counters under ``mtp_``."""
    if not parts or "mtp_loss" not in parts[0]:
        return {}
    main, mtp = (sum(float(p[k]) for p in parts) / len(parts)
                 for k in ("main_loss", "mtp_loss"))
    return {"main_loss": main, "mtp_loss": mtp,
            "mtp_loss_share": 100.0 * weight * mtp / (main + weight * mtp),
            **{"mtp_" + k: v for k, v in _expert_counters(
                [p["mtp_stats"] for p in parts]).items()}}


#: ``lm_loss_and_stats``'s parts a stack with state-space layers returns:
#: the chunks their scans walked a step
SCAN_PARTS = ("ssm_scan_chunks",)
#: ... and a stack with sparse layers: the layers' index losses summed, the
#: objective's other part, the index loss a layer, and — counted on the
#: device from the selections the attention used — the keys a query kept
#: (mean over queries and layers) and their share of the causal pairs, in
#: percent
INDEX_PARTS = ("index_loss", "main_loss", "index_loss_per_layer",
               "selected_keys_per_query", "selected_pair_share")


def _part_means(parts, names) -> dict:
    """Means over a run's steps of the parts ``names`` that its steps
    returned beside the loss; nothing for a stack whose steps return none
    (the first name tells)."""
    if not parts or names[0] not in parts[0]:
        return {}
    return {k: sum(float(p[k]) for p in parts) / len(parts) for k in names}


class Trainer:
    """Owns params + optimizer state and the jitted step.

    ``mesh`` (optional) applies ``param_shardings`` (tp over ``model``) to
    params AND optimizer state; tokens fed to ``step`` shard over
    ``data``.  Without a mesh everything is single-device.
    """

    def __init__(self, cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                 optimizer: str = "adamw", aux_weight: float = 0.01,
                 **opt_kwargs):
        self.cfg = cfg
        self.mesh = mesh
        self.optimizer = make_optimizer(optimizer, **opt_kwargs)
        self.aux_weight = aux_weight
        self._step_fn = None
        # host-side step counter for the fault/observability bus: the
        # device-side state.step would cost a sync per step to read.
        # Counts CONSUMED steps — with the input pipeline on, batches a
        # producer has rendered but the loop has not dispatched yet do
        # not advance it, so fault plans and the hang watchdog keep
        # their step semantics
        self._host_steps = 0
        # host-stall vs device-time split: step() books its token
        # reshard (the H2D transfer the pipeline hides) as stall
        self.meter = Throughput()
        self.pipeline_stats: dict = {}
        # what the last run() counted (the Word2Vec.train_metrics
        # analogue): the stall split, and with telemetry on the expert
        # layers' counters and the loss's parts, fetched with the loss
        self.train_metrics: dict = {}
        self._stats = []          # (MoEStats, loss parts) not yet fetched
        # serving plane: attach a serve.SnapshotPublisher here and
        # step() publishes a params-only snapshot every K steps (dense
        # params carry no key map — readers use the pytree directly)
        self.serve_publisher = None
        # control plane: attach a control.Controller here (dense params
        # have no placement knobs, so the useful mode is observe-only —
        # no sketch, no knobs — which emits control/evaluation events
        # with the traffic delta each cadence tick)
        self.controller = None
        # numerics health plane: arm_numerics() a NumericsCollector and
        # the step ships grad/update/param mass + nonfinite counts per
        # dispatch.  None (default) traces nothing extra
        self._numerics = None

    # -- state ------------------------------------------------------------
    def init_state(self, key) -> TrainState:
        """Parameters, their placement and the optimizer's state: the
        set-up span ``state_init`` (``obs.catalog.SETUP_SPANS``)."""
        with obs.setup_span("state_init"):
            return self._init_state(key)

    def _init_state(self, key) -> TrainState:
        params = init_params(key, self.cfg)
        if self.mesh is not None:
            shardings = param_shardings(params, self.cfg, self.mesh)
            params = jax.jit(lambda p: p, out_shardings=shardings)(params)
            # optimizer state mirrors param shapes -> mirror the shardings
            opt_state = jax.jit(
                self.optimizer.init,
                out_shardings=self._opt_shardings(params, shardings))(
                    params)
        else:
            opt_state = jax.jit(self.optimizer.init)(params)
        return TrainState(params, opt_state,
                          jnp.zeros((), jnp.int32))

    def _opt_shardings(self, params, param_sh):
        """Shardings for the optimizer state: optax states embed
        param-shaped pytrees (adam's mu/nu, sgd's trace) with the SAME
        treedef as the params — any subtree matching that structure gets
        the param shardings, everything else (counts, schedule steps)
        replicates."""
        shapes = jax.eval_shape(self.optimizer.init, params)
        repl = NamedSharding(self.mesh, P())
        params_treedef = jax.tree.structure(params)

        def walk(node):
            try:
                if jax.tree.structure(node) == params_treedef:
                    return param_sh
            except Exception:
                pass
            if isinstance(node, tuple) and hasattr(node, "_fields"):
                return type(node)(*(walk(v) for v in node))
            if isinstance(node, tuple):
                return tuple(walk(v) for v in node)
            if isinstance(node, list):
                return [walk(v) for v in node]
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            return repl

        return walk(shapes)

    # -- numerics health plane (obs/numerics.py) --------------------------
    def arm_numerics(self, collector) -> None:
        """Arm the numerics plane: ``collector`` (a
        ``NumericsCollector``) receives one bundle per dispatched step.
        Drops the compiled step — the bundle is baked in at trace
        time.  Call with None to disarm (also recompiles)."""
        self._numerics = collector
        self._step_fn = None

    # -- the step ---------------------------------------------------------
    def noise_key(self, step):
        """The key of step ``step``'s noise draw (objective
        ``block_diffusion``: ``models/diffusion.py::block_noise``), from
        the step count alone, so the draw of any step can be made again
        outside the step."""
        return jax.random.fold_in(jax.random.key(0), step)

    def _build_step(self):
        cfg, mesh, opt = self.cfg, self.mesh, self.optimizer
        aux_w = self.aux_weight
        num = self._numerics
        noised = cfg.objective == "block_diffusion"

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def train_step(params, opt_state, step, tokens):
            noise_key = None
            if noised:
                with obs.named_scope("noise"):
                    noise_key = self.noise_key(step)
            (loss, stats), grads = jax.value_and_grad(
                lm_loss_and_stats, has_aux=True)(
                    params, tokens, cfg, mesh, aux_weight=aux_w,
                    noise_key=noise_key)
            with obs.named_scope("optimizer"):
                updates, opt_state = opt.update(grads, opt_state, params)
                # neither a gradient nor weight decay moves a frozen leaf
                # (the selection bias; a share's router)
                updates = jax.tree_util.tree_map_with_path(
                    lambda path, u: jnp.zeros_like(u)
                    if is_frozen(path, cfg) else u, updates)
            if num is not None:
                from swiftmpi_tpu.obs import numerics as obs_numerics
                obs_numerics.stage_dense(num, params, grads, updates,
                                         loss)
            with obs.named_scope("optimizer"):
                params = optax.apply_updates(params, updates)
            return params, opt_state, step + 1, loss, stats

        return obs.costs.track("trainer_step", train_step)

    def step(self, state: TrainState, tokens, _book=None
             ) -> Tuple[TrainState, jax.Array]:
        """One train step.  Host spans, siblings in this order:
        ``step_prep`` (the step function and sharding checks), ``h2d``
        (host tokens only), ``dispatch``, ``step_book`` (counters, meter,
        recorder, publisher, controller, and ``run``'s own ``_book``).
        The step program is built in the set-up span ``step_build`` and
        its one call after that runs in ``first_step``
        (``obs.costs.TrackedFn``)."""
        n = self._host_steps
        reshard = False
        with obs.span("step_prep", step=n):
            faults.step_event(n)
            self._host_steps += 1
            if self._step_fn is None:
                with obs.setup_span("step_build"):
                    self._step_fn = self._build_step()
            if self.mesh is not None:
                want = NamedSharding(self.mesh, P("data", None))
                reshard = not (isinstance(tokens, jax.Array)
                               and tokens.sharding == want)
        if reshard:
            # reshard whatever we got so dp is never silently dropped;
            # booked as HOST STALL — this is the H2D transfer run() hides
            # by pre-transferring on the producer thread (pre-transferred
            # tokens skip this branch entirely).  Multi-process: host
            # tokens are this process's LOCAL rows of the global batch
            # (device_put would wrongly assume the same full value on
            # every host)
            with self.meter.stalling(), obs.span("h2d"):
                if jax.process_count() > 1:
                    tokens = jax.make_array_from_process_local_data(
                        want, np.asarray(tokens))
                else:
                    tokens = jax.device_put(jnp.asarray(tokens), want)
        elif self.mesh is None and not isinstance(tokens, jax.Array):
            with obs.span("h2d"):
                tokens = jnp.asarray(tokens)
        with obs.span("dispatch", step=n):
            params, opt_state, step, loss, stats = self._step_fn(
                state.params, state.opt_state, state.step, tokens)
        with obs.span("step_book", step=n):
            if obs.get_registry().enabled and (
                    self.cfg.n_experts or "ssm" in self.cfg.layer_ops
                    or "sparse" in self.cfg.layer_ops):
                self._stats.append(stats)
            self.meter.record(int(np.prod(tokens.shape)))
            obs.record_step(1)
            out = TrainState(params, opt_state, step)
            if self.serve_publisher is not None:
                self.serve_publisher.on_steps(out.params, n=1)
            if self.controller is not None:
                self.controller.on_steps(1)
            if _book is not None:
                _book(loss)
        return out, loss

    def run(self, state: TrainState, batches, pipeline: int = 0,
            dispatch_depth="auto") -> Tuple[TrainState, list]:
        """Consume an iterable of host token batches through ``step``.

        ``pipeline=K`` (single-process, meshed) prefetches K batches on
        a producer thread and eagerly ``device_put``s them with the
        step's committed ``P("data", None)`` input sharding, so H2D DMA
        overlaps the previous step's compute and ``step``'s reshard
        branch is skipped.  Loss scalars stay on device; a
        ``DispatchWindow`` (``dispatch_depth`` watermark) keeps the
        number of in-flight donated steps bounded.  Batch order and
        values are untouched, so ``pipeline=0`` is bit-identical.
        Returns ``(state, losses)`` with ``losses`` still device
        scalars — ``float()`` them after the epoch, not per step.

        Host spans as ``Word2Vec.train`` gives them
        (``obs.catalog.HOST_SPANS``): ``train_setup`` from entry to the
        first ``next``, ``input_wait`` around every ``next``, ``h2d`` and
        ``step_prep``, ``dispatch`` and ``step_book`` in ``step``,
        ``loss_fetch`` for the call's one blocking fetch (``loss_wait``
        for the last loss, then with telemetry on the read of the expert
        layers' counters, with a multi-token-prediction module of the
        loss's two parts, with state-space layers of the chunks their
        scans walked, and with sparse layers of the objective's two parts
        and the selections' counters), ``train_finish`` from there to the
        return.
        ``self.train_metrics`` holds what the call counted.
        """
        setup_span = obs.span("train_setup")
        setup_span.__enter__()
        stall0, steps0 = self.meter.host_stall_ms(), self._host_steps
        self._stats = []
        pipelined = (pipeline > 0 and self.mesh is not None
                     and jax.process_count() == 1)
        window = DispatchWindow(
            resolve_dispatch_bound(dispatch_depth, pipelined=pipelined))
        pipe = None
        it = batches
        if pipelined:
            from swiftmpi_tpu.io.pipeline import (PrefetchIterator,
                                                  device_put_transfer)
            want = NamedSharding(self.mesh, P("data", None))
            pipe = PrefetchIterator(it, depth=pipeline,
                                    transfer=device_put_transfer(want))
            it = pipe
        losses = []

        def book(loss):           # the loop's part of step_book
            losses.append(loss)
            window.push(loss)

        try:
            it = iter(it)
            while True:
                with self.meter.stalling():
                    if pipe is not None:    # its __next__ has the span
                        tokens = next(it, None)
                    else:
                        with obs.span("input_wait") as wait:
                            tokens = next(it, None)
                            if tokens is None:
                                wait.drop()
                if setup_span is not None:
                    setup_span.__exit__(None, None, None)
                    setup_span = None
                if tokens is None:
                    break
                state, _ = self.step(state, tokens, book)
        finally:
            if setup_span is not None:
                setup_span.__exit__(None, None, None)
            if pipe is not None:
                pipe.close()
                self.pipeline_stats = pipe.stats()
        with obs.span("loss_fetch"):
            # the wait first, so that it is the wait and the read a read
            with obs.span("loss_wait"):
                if losses:
                    jax.block_until_ready(losses[-1])
            stats, self._stats = jax.device_get(self._stats), []
        with obs.span("train_finish"):
            steps = self._host_steps - steps0
            stall = self.meter.host_stall_ms() - stall0
            self.train_metrics = {
                "steps": steps, "host_stall_ms": stall,
                "stall_ms_per_step": stall / steps if steps else 0.0,
                **(_expert_counters([s for s, _parts in stats])
                   if self.cfg.n_experts else {}),
                **_loss_parts([p for _s, p in stats], self.cfg.mtp_weight),
                **_part_means([p for _s, p in stats], SCAN_PARTS),
                **_part_means([p for _s, p in stats], INDEX_PARTS)}
        obs.log_setup_once()     # start-up, said once a process
        return state, losses

    # -- checkpoints (multihost-safe, atomic, CRC-validated) ---------------
    def save(self, state: TrainState, path: str, retain: int = 1) -> None:
        from swiftmpi_tpu import obs
        with obs.span("checkpoint_save"):
            self._save(state, path, retain)

    def _save(self, state: TrainState, path: str, retain: int) -> None:
        flat, treedef = jax.tree.flatten(state.tree())
        # every process gathers (host_array is a collective); only the
        # writer touches the disk — and logs from the gathered copy, so no
        # collective runs after non-writers have returned
        payload = {f"leaf_{i}": host_array(v) for i, v in enumerate(flat)}
        if not is_writer():
            return
        payload["treedef"] = np.frombuffer(
            repr(treedef).encode(), dtype=np.uint8)
        dst = npz_path(path)
        rotate_before_write(dst, retain)
        atomic_savez(dst, payload)
        prune_generations(dst, retain)
        step_i = next(i for i, v in enumerate(flat) if v is state.step)
        log.info("trainer checkpoint -> %s (step %d)", dst,
                 int(payload[f"leaf_{step_i}"]))
        faults.checkpoint_event(dst)

    def load(self, path: str, key=None, verify: bool = True) -> TrainState:
        """Rebuild a TrainState from ``save`` output.  The tree structure
        comes from a fresh ``init_state`` (cfg must match); leaf order is
        the flatten order, so shapes are validated leaf-by-leaf.
        ``verify`` CRC-checks every array first (CheckpointCorruptError
        on a torn/bit-rotted file) — restoring damaged optimizer state
        silently poisons the whole downstream run."""
        state = self.init_state(key if key is not None
                                else jax.random.key(0))
        flat, treedef = jax.tree.flatten(state.tree())
        dst = npz_path(path)
        if verify:
            verify_checkpoint(dst)
        with np.load(dst) as z:
            saved_def = z["treedef"].tobytes().decode()
            if saved_def != repr(treedef):
                raise ValueError(
                    "checkpoint state tree does not match this trainer "
                    "(optimizer/config mismatch?): saved "
                    f"{saved_def[:120]}... != {repr(treedef)[:120]}...")
            loaded = [z[f"leaf_{i}"] for i in range(len(flat))]
        for i, (have, want) in enumerate(zip(loaded, flat)):
            if tuple(have.shape) != tuple(want.shape):
                raise ValueError(
                    f"checkpoint leaf {i} shape {have.shape} != "
                    f"model {tuple(want.shape)} (config mismatch?)")
        def put(arr, ref):
            if isinstance(ref, jax.Array):
                # make_array_from_callback works for multi-process global
                # shardings too (device_put would require addressability)
                return jax.make_array_from_callback(
                    arr.shape, ref.sharding, lambda idx: arr[idx])
            return arr

        tree = jax.tree.unflatten(
            treedef, [put(a, r) for a, r in zip(loaded, flat)])
        return TrainState(tree["params"], tree["opt_state"], tree["step"])
