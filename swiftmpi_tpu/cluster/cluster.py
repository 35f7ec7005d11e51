"""Cluster orchestrator: bring-up and tear-down around a training run.

Equivalent of the reference ``Cluster<WorkerT, ServerT, KeyT>``
(`/root/reference/src/cluster/cluster.h:9-140`), with the bootstrap collapsed
to mesh construction: where ``initialize()`` there exchanges ports over
MPI_Allgather and registers N×M ZMQ routes, here it builds the device mesh
and the hashfrag routing table; ``finalize(path)`` there barriers and dumps
the server tables — here it flushes registered tables through the checkpoint
writer (no barriers needed: host-side dispatch order is the barrier).

Config surface mirrors the reference ``[cluster]`` section
(cluster/cluster.h:13-25 + demo.conf):

* ``server_num``   — number of table shards (the ``model``/``shard`` axis
  size; the reference's inverted present/absent branch is NOT replicated —
  absent means "all devices").
* ``transfer``     — data-plane backend (``xla``/``tpu``/``hybrid``/
  ``local``), the BASELINE.json north-star flag.
* ``frag_num``     — hashfrag granularity (``[server]`` section, like the
  reference server.frag_num).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax

from swiftmpi_tpu.cluster.bootstrap import init_distributed
from swiftmpi_tpu.cluster.hashfrag import HashFrag
from swiftmpi_tpu.cluster.mesh import (MODEL_AXIS, SHARD_AXIS, MeshSpec,
                                       build_mesh, mesh_info, ps_mesh)
from swiftmpi_tpu.parameter.access import AccessMethod
from swiftmpi_tpu.parameter.key_index import KeyIndex
from swiftmpi_tpu.parameter.sparse_table import SparseTable
from swiftmpi_tpu.transfer.api import Transfer, get_transfer
from swiftmpi_tpu.utils.config import ConfigParser, global_config
from swiftmpi_tpu.utils.logger import get_logger

log = get_logger(__name__)


class Cluster:
    def __init__(self, config: Optional[ConfigParser] = None,
                 devices: Optional[List[jax.Device]] = None):
        self.config = config if config is not None else global_config()
        self._devices = devices
        self.mesh = None
        self.hashfrag: Optional[HashFrag] = None
        self.transfer: Optional[Transfer] = None
        self.tables: Dict[str, SparseTable] = {}
        self._initialized = False

    # -- bring-up (cluster.h:27-30) ----------------------------------------
    def initialize(self) -> "Cluster":
        # MPI_Init equivalent: join the coordinator if the launcher/pod
        # scheduler named one (no-op otherwise; see cluster/bootstrap.py)
        multi_process = init_distributed(self.config)
        devices = list(jax.devices() if self._devices is None
                       else self._devices)
        n_servers = (self.config.get("cluster", "server_num").to_int32()
                     if self.config.has("cluster", "server_num")
                     else len(devices))
        backend = (self.config.get("cluster", "transfer").to_string()
                   if self.config.has("cluster", "transfer") else "xla")
        if backend in ("tpu", "hybrid"):
            # explicit routing wants the both-roles mesh: every device is
            # worker+server.  Single-process: 1-D, shard count == device
            # count.  Multi-process: hybrid (data x shard) — the shard
            # routing axis stays within each process (ICI), data groups
            # replicate the table and reconcile via one dense psum per
            # push (the only DCN traffic).  See ps_mesh/TpuTransfer.
            # ``hybrid`` shares the mesh: its tail path IS the tpu
            # routing, its hot head is replicated over every axis.
            self.mesh = ps_mesh(devices=devices, hybrid=multi_process)
            shard_size = int(self.mesh.shape[SHARD_AXIS])
            if (n_servers != shard_size
                    and self.config.has("cluster", "server_num")):
                log.warning(
                    "transfer=%s sizes the server count by its shard "
                    "axis; overriding server_num=%d -> %d", backend,
                    n_servers, shard_size)
            self.table_axis = SHARD_AXIS
            n_servers = shard_size
        else:
            if len(devices) % n_servers:
                raise ValueError(
                    f"server_num={n_servers} must divide "
                    f"{len(devices)} devices")
            # multi-process: keep the data axis outermost across hosts so
            # table-shard collectives ride ICI and only dp crosses DCN
            self.mesh = build_mesh(
                MeshSpec.from_dict({"data": -1, "model": n_servers}),
                devices=devices, hybrid=multi_process)
            self.table_axis = MODEL_AXIS
        self.n_servers = n_servers
        frag_num = (self.config.get("server", "frag_num").to_int32()
                    if self.config.has("server", "frag_num") else None)
        self.hashfrag = HashFrag(n_servers, frag_num)
        kwargs = ({"mesh": self.mesh}
                  if backend in ("tpu", "hybrid")
                  else {"shards": n_servers,
                        "platform": devices[0].platform,
                        "mesh": self.mesh, "axis": self.table_axis}
                  if backend == "xla" else {})
        self.transfer = get_transfer(backend, **kwargs)
        self._initialized = True
        log.info("cluster up: %s transfer=%s", mesh_info(self.mesh), backend)
        return self

    # -- tables ------------------------------------------------------------
    def create_table(self, name: str, access: AccessMethod,
                     capacity_per_shard: int, seed: int = 0,
                     partition=None) -> SparseTable:
        """``partition``: optional ``HotColdPartition`` reserving a
        replicated hot head in the table (hybrid transfer); tail keys
        keep the hashfrag-sharded layout.  The set-up span
        ``table_create``: the key index's frame and the table's jitted
        ``init_all`` (``SparseTable._init_state``)."""
        from swiftmpi_tpu import obs      # obs imports this package

        if not self._initialized:
            raise RuntimeError("Cluster.initialize() first")
        with obs.setup_span("table_create"):
            ki = KeyIndex(self.n_servers, capacity_per_shard,
                          hashfrag=self.hashfrag, partition=partition)
            table = SparseTable(access, ki, mesh=self.mesh,
                                axis=self.table_axis, seed=seed)
        self.tables[name] = table
        return table

    # -- tear-down (cluster.h:41-54) ---------------------------------------
    def finalize(self, path: Optional[str] = None,
                 formatter=None) -> None:
        """Dump registered tables as text checkpoints (reference
        SparseTable::output, sparsetable.h:119-132) and drop them."""
        if path is not None:
            from swiftmpi_tpu.io.checkpoint import dump_table_text
            for name, table in self.tables.items():
                out = path if len(self.tables) == 1 else f"{path}.{name}"
                dump_table_text(table, out, formatter=formatter)
                log.info("finalize: dumped table %s -> %s", name, out)
        self.tables.clear()
        self._initialized = False
