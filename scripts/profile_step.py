#!/usr/bin/env python
"""On-device phase ablation of the fused w2v CBOW step (VERDICT round-1
'next' #2: profile before optimizing).

Times progressively larger slices of the step at bench.py's shapes so the
per-phase cost falls out by subtraction:

  a. gathers only            (pull h_t + v_ctx, reduce to scalar)
  b. + einsum/grad math      (neu1, f, g, contribs, err)
  c. + push assembly         (family layout; mean-norm now lives in push)
  d. full step               (+ transfer.push dense/sparse + AdaGrad)

plus the roofline context (bytes moved per phase at fp32) printed next to
each measurement.  Run (on the chip): python scripts/profile_step.py
(or JAX_PLATFORMS=cpu ... for the host baseline).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402


def _build_1m(dev):
    """The 1M-vocab cell's exact device-side shape as a single batch —
    the ablation target for round-3 verdict Next #4: what fraction of
    the 90.4ms step is the capacity-range gather vs scatter vs
    sampling.  Model construction is bench.build_w2v_1m_model, the SAME
    builder the timed cell uses, so a cell retune can't silently
    desynchronize the profiled shape (review finding)."""
    import jax.numpy as jnp
    import bench

    model, rng = bench.build_w2v_1m_model(dev)
    V = bench.W2V_1M_VOCAB
    B, W2 = bench.BATCH, 2 * model.window
    centers = jnp.asarray(rng.integers(0, V, size=(B,)), jnp.int32)
    contexts = jnp.asarray(rng.integers(0, V, size=(B, W2)), jnp.int32)
    mask = jnp.asarray(rng.random((B, W2)) < 0.8)
    return model, centers, contexts, mask


def main():
    import jax
    import jax.numpy as jnp
    import bench

    dev = jax.devices()[0]
    print(f"device: {dev}", flush=True)
    if os.environ.get("PROFILE_SCALE") == "1m":
        model, centers, contexts, mask = _build_1m(dev)
        centers = jax.device_put(centers, dev)
        contexts = jax.device_put(contexts, dev)
        mask = jax.device_put(mask, dev)
        print(f"shape: 1M vocab, capacity {model.table.capacity}",
              flush=True)
    else:
        model, step, batches = bench._build_w2v(dev)
        b0 = batches[0]
        centers = jax.device_put(jnp.asarray(b0.centers), dev)
        contexts = jax.device_put(jnp.asarray(b0.contexts), dev)
        mask = jax.device_put(jnp.asarray(b0.ctx_mask), dev)
    d = model.len_vec
    K = model.negative
    B = bench.BATCH
    W2 = 2 * model.window
    cap = model.table.capacity

    state = {f: jax.device_put(v, dev) for f, v in model.table.state.items()}
    sov = jax.device_put(model._slot_of_vocab, dev)
    ap = jax.device_put(model._alias_prob, dev)
    ai = jax.device_put(model._alias_idx, dev)
    key = jax.random.key(3)

    from swiftmpi_tpu.models.word2vec import _assemble_push, _cbow_targets
    from swiftmpi_tpu.ops.sampling import sample_alias_slots
    from swiftmpi_tpu.ops.sigmoid import sigmoid_clipped

    def phase_a0(state, key):
        # sampling alone (the fused (V,4)-row draw, as the real step
        # samples — round-3's biggest single step win; this cell is the
        # before/after record)
        negs, neg_slots = sample_alias_slots(key, ap, ai, sov, (B, K))
        return negs.sum() + neg_slots.sum() + state["h"][0, 0]

    def phase_a(state, key):
        # target assembly + row pulls, via the SAME shared helper the
        # real step uses (_cbow_targets) so this ablation can't drift
        # from the production phase structure
        t_slots, ctx_slots, t_valid = _cbow_targets(
            sov, ap, ai, centers, contexts, mask, key, K)
        h_t = jnp.take(state["h"], jnp.clip(t_slots.reshape(-1), 0, cap - 1),
                       axis=0)
        v_ctx = jnp.take(state["v"],
                         jnp.clip(ctx_slots.reshape(-1), 0, cap - 1), axis=0)
        return h_t.sum() + v_ctx.sum()

    def _grads(state, key):
        t_slots, ctx_slots, t_valid = _cbow_targets(
            sov, ap, ai, centers, contexts, mask, key, K)
        t_slots = jnp.where(t_valid, t_slots, -1)
        h_t = jnp.take(state["h"], jnp.clip(t_slots.reshape(-1), 0, cap - 1),
                       axis=0).reshape(B, K + 1, d)
        v_ctx = jnp.take(
            state["v"], jnp.clip(ctx_slots.reshape(-1), 0, cap - 1),
            axis=0).reshape(B, W2, d)
        neu1 = jnp.sum(v_ctx * mask[..., None], axis=1)
        f = jnp.einsum("bd,bkd->bk", neu1, h_t)
        g = (jnp.concatenate([jnp.ones((B, 1)), jnp.zeros((B, K))], axis=1)
             - sigmoid_clipped(f)) * model.alpha
        g = jnp.where(t_valid, g, 0.0)
        h_contrib = g[..., None] * neu1[:, None, :]
        neu1e = jnp.einsum("bk,bkd->bd", g, h_t)
        v_contrib = jnp.where(mask[..., None], neu1e[:, None, :], 0.0)
        return (t_slots, ctx_slots, h_contrib, v_contrib,
                jnp.sum(1e4 * g * g))

    def phase_b(state, key):
        t_slots, ctx_slots, h_c, v_c, err = _grads(state, key)
        return h_c.sum() + v_c.sum() + err

    def phase_c(state, key):
        t_slots, ctx_slots, h_c, v_c, err = _grads(state, key)
        pushes = _assemble_push(t_slots.reshape(-1), ctx_slots.reshape(-1),
                                h_c.reshape(-1, d), v_c.reshape(-1, d))
        return sum(g.sum() for _, gr, _m in pushes
                   for g in gr.values()) + err

    def phase_d(state, key):
        t_slots, ctx_slots, h_c, v_c, err = _grads(state, key)
        pushes = _assemble_push(t_slots.reshape(-1), ctx_slots.reshape(-1),
                                h_c.reshape(-1, d), v_c.reshape(-1, d))
        for slots, grads, mean in pushes:
            state = model.transfer.push(state, slots, grads, model.access,
                                        mean=mean)
        return state["h"].sum() + err

    nt, nc = B * (K + 1), B * W2
    mb = 1e-6 * 4
    notes = {
        "a_gathers": f"~{(nt + nc) * d * mb:.0f} MB gathered",
        "b_+gradmath": f"+{(nt + nc) * d * mb:.0f} MB contribs",
        "c_+meanscale": f"+{(nt + nc) * 2 * 4e-6:.0f} MB counts",
        "d_full_step": f"+scatter {(nt + nc) * d * mb:.0f} MB + "
                       f"AdaGrad sweep {cap * d * 4 * 2 * mb:.0f} MB",
    }
    reps = int(os.environ.get("PROFILE_REPS", "8"))
    notes["a0_sampling"] = f"~{B * K * 16e-6:.0f} MB packed rows"
    for name, fn in (("a0_sampling", phase_a0),
                     ("a_gathers", phase_a), ("b_+gradmath", phase_b),
                     ("c_+meanscale", phase_c), ("d_full_step", phase_d)):
        jf = jax.jit(fn)
        out = jf(state, key)
        float(np.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
        t0 = time.perf_counter()
        for i in range(reps):
            out = jf(state, jax.random.fold_in(key, i))
        float(np.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
        dt = (time.perf_counter() - t0) / reps
        # XLA's own count next to the hand note (ISSUE 14): the catalog's
        # cost_analysis sees through fusion, so where the two disagree
        # the hand model is the suspect — the subtraction ablation above
        # stays the phase-attribution source of truth
        xla = _xla_note(jf, state, key)
        print(f"{name:14s} {dt * 1e3:8.2f} ms   ({notes[name]}"
              f"{xla})", flush=True)


def _xla_note(jf, state, key) -> str:
    """`` | xla: N MB, M GFLOP`` from the jit's own cost_analysis —
    best-effort (a backend without the analysis just drops the note)."""
    try:
        ca = jf.lower(state, key).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        b = float(ca.get("bytes accessed", 0.0))
        f = float(ca.get("flops", 0.0))
        if b > 0 or f > 0:
            return f" | xla: {b * 1e-6:.0f} MB, {f * 1e-9:.2f} GFLOP"
    except Exception:
        pass
    return ""


if __name__ == "__main__":
    main()
