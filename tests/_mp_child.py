"""Child program for the multi-process launcher test (not a pytest file).

Run under ``python -m swiftmpi_tpu.launch -np 2 -cpu 2 -- python
tests/_mp_child.py``: joins the coordinator through the normal
``Cluster.initialize()`` path, checks the global device view, runs a
cross-process reduction, and hits the barrier — the whole MPI-equivalent
control+data plane in one pass.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P     # noqa: E402

from swiftmpi_tpu.cluster import (Cluster, barrier, process_count,  # noqa
                                  process_index, shutdown_distributed)
from swiftmpi_tpu.utils import ConfigParser                    # noqa: E402


def main():
    cfg = ConfigParser().update(
        {"cluster": {"transfer": "xla", "server_num": 1}})
    cluster = Cluster(cfg).initialize()

    nprocs = process_count()
    assert nprocs == int(os.environ["SMTPU_NUM_PROCESSES"]), \
        f"joined {nprocs} processes"
    n = len(jax.devices())
    assert n == nprocs * jax.local_device_count()

    # cross-process reduction: every device holds its global position;
    # the replicated sum must see all of them (DCN-equivalent collective)
    mesh = cluster.mesh
    data = np.arange(n, dtype=np.float32)
    arr = jax.make_array_from_callback(
        (n,), NamedSharding(mesh, P("data")), lambda idx: data[idx])
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
    want = n * (n - 1) / 2
    assert float(total) == want, f"{float(total)} != {want}"

    # default config (server_num absent -> every device a server): the
    # data axis is 1, so the DCN granule must move to a divisible axis
    # instead of failing bring-up
    default_cluster = Cluster(ConfigParser()).initialize()
    assert default_cluster.mesh.devices.size == n

    # one REAL training step across processes: identical host batches on
    # every process, dp-sharded over the global data axis, table updates
    # through the jitted step (the reference's distributed SGD epoch body)
    from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus
    from swiftmpi_tpu.models.word2vec import Word2Vec, _Tally

    cfg.update({"word2vec": {"len_vec": 8, "window": 2, "negative": 2,
                             "sample": -1, "learning_rate": 0.05},
                "server": {"initial_learning_rate": 0.3, "frag_num": 64},
                "worker": {"minibatch": 32}})
    model = Word2Vec(config=cfg, cluster=cluster)
    corpus = synthetic_corpus(8, vocab_size=32, length=12, seed=0)
    model.build(corpus)
    batch = next(CBOWBatcher(corpus, model.vocab, model.window).epoch(
        4 * n))
    step = model._build_step()

    def global_put(x, spec):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, NamedSharding(mesh, spec), lambda idx: x[idx])

    state = model.table.state
    new_state, _key, tally, es = step(
        state, model._slot_of_vocab, model._alias_prob, model._alias_idx,
        global_put(batch.centers, P("data")),
        global_put(batch.contexts, P("data", None)),
        global_put(batch.ctx_mask, P("data", None)),
        jax.random.key(1))
    jax.block_until_ready(new_state)
    model.table.state = new_state   # the step donated the old buffers
    loss = float(es) / max(_Tally.read(tally)["pair_count"], 1)
    assert np.isfinite(loss), f"non-finite loss {loss}"

    # full distributed epoch through the public API: train() shards the
    # corpus per process, wraps the batcher in DistributedBatcher, and
    # runs lockstep global steps until the fastest shard drains
    losses = model.train(corpus, niters=1, batch_size=2 * n)
    assert len(losses) == 1 and np.isfinite(losses[0]), losses

    # transfer=tpu across processes: hybrid (data x shard) mesh — shard
    # routing stays within each process, data groups reconcile via one
    # dense psum per push.  Verify pull/push against the numpy oracle.
    from swiftmpi_tpu.cluster.mesh import DATA_AXIS, SHARD_AXIS
    from swiftmpi_tpu.transfer.local import LocalTransfer
    from swiftmpi_tpu.parameter import w2v_access

    tcfg = ConfigParser().update(
        {"cluster": {"transfer": "tpu"}, "server": {"frag_num": 64}})
    tcluster = Cluster(tcfg).initialize()
    tmesh = tcluster.mesh
    assert DATA_AXIS in tmesh.axis_names, tmesh
    assert int(tmesh.shape[DATA_AXIS]) == nprocs
    assert int(tmesh.shape[SHARD_AXIS]) == jax.local_device_count()
    access = w2v_access(0.3, 8)
    table = tcluster.create_table("t", access, capacity_per_shard=32)
    keys = np.arange(24, dtype=np.uint64)
    slots = table.key_index.lookup(keys)
    pulled = tcluster.transfer.pull(table.state, slots, access)
    # global batch: every process passed the same host slots array, which
    # the shard_map shards over (data, shard) — results replicated back
    from swiftmpi_tpu.cluster.bootstrap import host_array
    got_h = host_array(pulled["h"])
    state_h = host_array(table.state["h"])
    want = LocalTransfer().pull({"h": state_h, "v": host_array(
        table.state["v"])}, slots, access)
    np.testing.assert_allclose(got_h, want["h"], rtol=1e-6)
    grads = {f: np.ones((24, 8), np.float32) for f in access.grad_fields}
    new_state = tcluster.transfer.push(table.state, slots, grads, access)
    # every dp group pushed the same grads; the psum multiplies by nprocs
    want_new = LocalTransfer().push(
        {f: host_array(v) for f, v in table.state.items()}, slots,
        {f: float(nprocs) * g for f, g in grads.items()}, access)
    np.testing.assert_allclose(host_array(new_state["h"]),
                               want_new["h"], rtol=1e-5, atol=1e-6)

    # one REAL w2v training step through the explicit tpu backend on the
    # hybrid mesh: per-family pushes, all_to_all routing on the local
    # shard axis, the dp psum reconciling the table replicas
    tcfg.update({"word2vec": {"len_vec": 8, "window": 2, "negative": 2,
                              "sample": -1, "learning_rate": 0.05},
                 "server": {"initial_learning_rate": 0.3, "frag_num": 64},
                 "worker": {"minibatch": 32}})
    tmodel = Word2Vec(config=tcfg, cluster=tcluster)
    tmodel.build(corpus)
    tb = next(CBOWBatcher(corpus, tmodel.vocab, tmodel.window).epoch(
        2 * n))
    tstep = tmodel._build_step()
    tstate, _key, ttally, tes = tstep(
        tmodel.table.state, tmodel._slot_of_vocab, tmodel._alias_prob,
        tmodel._alias_idx, jnp.asarray(tb.centers),
        jnp.asarray(tb.contexts), jnp.asarray(tb.ctx_mask),
        jax.random.key(5))
    tmodel.table.state = tstate
    tloss = float(tes) / max(_Tally.read(ttally)["pair_count"], 1)
    assert np.isfinite(tloss), f"tpu-transfer step loss {tloss}"
    changed = host_array(tstate["h"])
    assert np.abs(changed).sum() > 0

    barrier("mp_child_done")
    print(f"MP_OK proc={process_index()}/{nprocs} devices={n} "
          f"sum={float(total)} loss={loss:.4f} "
          f"epoch_err={losses[0]:.4f} tpu_transfer_ok=1 "
          f"tpu_step_loss={tloss:.4f}", flush=True)
    shutdown_distributed()


if __name__ == "__main__":
    main()
