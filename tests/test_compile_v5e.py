"""Real-size programs compiled for a v5e chip that is described, not
attached (on-chip-measurement guide, section 2): costs no chip time, says
nothing about results or times.

One file for every such test: only one process may load the TPU's
compiler, and the topology is described inside a fixture so that every
worker collects the same tests and only the one given this file loads it.
"""

import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cell(name):
    """(configuration, traffic) of a cell of BENCHMARK.json, as data."""
    def load(*path):
        with open(os.path.join(REPO, *path)) as f:
            return json.load(f)
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (load(config["file"]),
            load("benchmark", "traffic", cell["traffic"] + ".json"))


def _shapes_only(self):
    """``SparseTable._init_state`` for a chip that is not there: no array
    can be placed on it, so shapes with the table's own shardings (and,
    passing none, the compiler's default layout, which is what the table's
    arrays have on the chip)."""
    return {n: jax.ShapeDtypeStruct((self.key_index.capacity, fs.dim),
                                    fs.dtype, sharding=self.field_sharding(n))
            for n, fs in self.access.fields.items()}


def _load_conf(path, config, minibatch):
    """The conf the benchmark's families write: the configuration's
    ``[word2vec]`` / ``[server]`` keys and the traffic's minibatch, every
    mechanism of the program at its default."""
    from swiftmpi_tpu.utils import global_config, reset_global_config

    path.write_text("\n".join(
        ["[word2vec]", *(f"{k}: {v}" for k, v in config["word2vec"].items()),
         "[server]", *(f"{k}: {v}" for k, v in config["server"].items()),
         "[worker]", f"minibatch: {minibatch}"]) + "\n")
    reset_global_config()
    return global_config().load_conf(str(path)).parse()


TILES = dict.fromkeys(("h", "h2sum", "v", "v2sum"), "tiles")


def _span_positions(config, traffic, centers):
    """The span the native batcher would cut for the cell's stream: its
    own rule (``data/text.py::span_positions``) on the expected counts of
    the traffic's key law (every key once, the rest Zipf or uniform)."""
    import numpy as np

    from swiftmpi_tpu.data.text import center_keep_mean, span_positions
    from swiftmpi_tpu.ops.sampling import subsample_keep_prob

    vocab, keys = int(config["vocab_size"]), traffic["keys"]
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
        keys.get("exponent", 0.0))
    counts = 1.0 + (int(traffic["stream_tokens"]) - vocab) * p / p.sum()
    keep = subsample_keep_prob(counts, float(config["word2vec"]["sample"]))
    return span_positions(centers, int(config["word2vec"]["window"]),
                          center_keep_mean(counts, keep))


def _w2v_step(topo, tmp_path, monkeypatch, cell, chips=1):
    """(cluster, model, compiled train step) of a word2vec cell on
    ``chips`` described v5e chips, the model built by the calls the
    benchmark's families make."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.parameter import sparse_table

    config, traffic = _cell(cell)
    w2v = config["word2vec"]
    vocab, window = int(config["vocab_size"]), int(w2v["window"])
    minibatch = int(traffic.get("minibatch")
                    or traffic["centers_per_step"] * 2 * window)
    centers = max(256, minibatch // (2 * window))
    conf = _load_conf(tmp_path / "cell.conf", config, minibatch)

    monkeypatch.setattr(sparse_table.SparseTable, "_init_state",
                        _shapes_only)
    cluster = Cluster(conf, devices=list(topo.devices)[:chips]).initialize()
    model = Word2Vec(cluster=cluster)
    # Word2Vec.build_from_vocab's capacity rule
    capacity = max(64, int(vocab * 1.3 / cluster.n_servers) + 1)
    model.table = cluster.create_table("w2v", model.access, capacity)
    # ... and its rule for the context side's rendering
    model._resolve_stencil()
    step = model._build_step()
    rep = NamedSharding(cluster.mesh, P())

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)

    if model.stencil:
        # the span batch as the native batcher packs it
        model.span = _span_positions(config, traffic, centers)
        batch = (shape((2 * model.span + 2 * centers,), jnp.int32),)
        statics = {"centers": centers}
    else:
        batch = (shape((centers,), jnp.int32),
                 shape((centers, 2 * window), jnp.int32),
                 shape((centers, 2 * window), jnp.bool_))
        statics = {}
    key = jax.eval_shape(lambda: jax.random.key(0))
    # the step's carried arguments (ISSUE 44): the model's key and the
    # call's tally, donated like the state
    from swiftmpi_tpu.models.word2vec import _Tally
    tally = _Tally.zeros()
    compiled = step.lower(
        model.table.state, shape((vocab,), jnp.int32),
        shape((vocab,), jnp.float32), shape((vocab,), jnp.int32), *batch,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep),
        shape(tally.shape, tally.dtype), **statics).compile()
    return cluster, model, compiled


def _aliased(text):
    """{(output, parameter)} of a compiled module's input_output_alias."""
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    return {(int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", aliased)}


def _carried_aliased(text):
    """The four fields, the key and the tally: each result written where
    its argument was (results: fields 0-3, key data 4, tally 5, the
    step's own error sum last; the key and the tally are the last two
    parameters)."""
    n_params = len(re.findall(r"parameter\((\d+)\)", re.search(
        r"\nENTRY [^\n]*\{\n(.*?)\n\}", text, re.S).group(1)))
    return _aliased(text) >= {(i, i) for i in range(4)} | {
        (4, n_params - 2), (5, n_params - 1)}


def _pair_grid(model, traffic):
    """Slots of the ``(B, 2W)`` context pair grid a per-pair step of the
    cell gathers, sorts and pushes."""
    minibatch = int(traffic.get("minibatch")
                    or traffic["centers_per_step"] * 2 * model.window)
    return max(256, minibatch // (2 * model.window)) * 2 * model.window


def _instructions(text):
    """(count, digest) of a compiled module's instructions: names,
    operands and layouts, the metadata's source lines aside — and a
    Pallas kernel's serialized body, which carries its own."""
    lines = [re.sub(r", metadata=\{[^}]*\}", "", re.sub(
                 r'"custom_call_config":\{"body":"[^"]*"', "", line.rstrip()))
             for line in text.splitlines()
             if re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = ", line)]
    return (len(lines),
            hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16])


@pytest.mark.parametrize("cell, temp_gib, span, program", [
    # 5,500 target slots and a span of 768: pushes too small to be
    # ordered behind the state (`_ORDERED_PUSH_BYTES`; with the barrier
    # this step hangs the chip, PERF.md section 6, PR 47)
    ("cbow2m-demo", 0.01, 768, (3088, "40e61a3335720dfa")),
    # 180,224 target slots: the longest head a one-chip cell pushes, which
    # the parent swept where more than ~116 K of them were distinct; the
    # context push is the span's 22,528 slots (74.2 % of a Zipf stream's
    # positions pass the center gate)
    ("cbow2m-b16k", 1.0, 22_400, (3229, "12b7f3d9826a0763")),
    # uniform keys: nothing is gated, the span is B + 2W in whole tiles
    ("cbow2m-b16k-uniform", 1.0, 16_512, (3231, "e99a5c4c8ee5e633")),
    # 122,880 target slots, 20,480 input slots
    ("sg2m-b2k", 0.7, None, (2830, "25cee493170ff845")),
])
def test_w2v_step_copies_no_field(topo, no_compile_cache, tmp_path,
                                  monkeypatch, cell, temp_gib, span,
                                  program):
    """A word2vec cell's train step at 2,340,001 rows on one v5e chip.
    The 300-wide rows are stored on 384 lanes (`access.stored_width`), so
    with no layout asked for the four fields come in and go out row-major
    (``{1,0:T(8,128)}``, the compiler's default at that width) and
    aliased, NO whole field is copied (the 300-wide table: 11 copies a
    step, 3.37 GiB of temporaries = one padded row-major field, ~108 ms)
    — not around the tile kernel, whose fields are aliased in to out
    (PR 47) —, the step fits the chip, and every push writes back the
    rows at its head (`XlaTransfer.write_back_form`) by their 8-row tiles:
    one kernel a push under the ``apply`` scope, no scatter, no sweep, no
    loop and no conditional of XLA's around a field (PR 34's forms)."""
    cluster, model, compiled = _w2v_step(topo, tmp_path, monkeypatch, cell)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    capacity = model.table.capacity

    assert (capacity, model.len_vec, model.row_width) == (2_340_001, 300, 384)
    # a CBOW cell renders its contexts over the span: the step holds NO
    # operation at the shape of the (B, 2W) pair grid (ISSUE 36; 15.1 ms
    # of cbow2m-b16k's 70.7 were five of them); skip-gram is per-pair
    grid = _pair_grid(model, _cell(cell)[1])
    assert bool(model.stencil) == (span is not None) == (not model.sg)
    if span is not None:
        assert model.span == span
        centers = grid // (2 * model.window)
        assert f"s32[{2 * span + 2 * centers}]" in text    # one packed batch
        assert re.findall(rf"f32\[{span},384\]", text)
        # no row, gradient or mask of the grid.  What is left at its slot
        # count is the sampler's: this configuration draws K = 2W = 10
        # negatives a center, so the (B, K) draw has as many slots
        assert model.negative == 2 * model.window
        assert f"[{grid},384]" not in text
        at_grid = re.findall(rf"= (\w+)\[{grid}[\],].*", text)
        assert set(at_grid) <= {"s32", "u32"}
        assert all("/sample/" in line for line in re.findall(
            rf"= \w+\[{grid}[\],].*op_name=\"([^\"]*)\"", text))
    else:
        assert re.findall(rf"= f32\[{grid},384\]", text)
    # ... and the step is PR 44's instruction for instruction: PR 42's
    # (skip-gram's: PR 35's), where a table on ONE shard is pulled and
    # pushed directly and the owner routing of ISSUE 43 is bypassed, plus
    # the bookkeeping ISSUE 44 moved into the program — the key's split
    # (threefry, whose constants the sampler's now share) and the tally's
    # adds: 366 instructions more, every one outside the pull, math,
    # dedup and apply scopes, whose instructions count as they did.  A PR
    # that changes a step on purpose pins its own digest here.
    assert _instructions(text) == program
    field = rf"f32\[{capacity},384\]"
    # the module's first line: aliasing and the entry's layouts
    params, results = re.search(r"entry_computation_layout=\{(.*)",
                                text).group(1).split(")->(")
    row_major = field + r"\{1,0:T\(8,128\)\}"
    assert len(re.findall(row_major, params)) == 4
    assert len(re.findall(row_major, results)) == 4
    assert _carried_aliased(text)
    assert not re.findall(rf"= {field}\S* copy\(", text)
    assert f"[{capacity},300]" not in text
    # a field is 3.35 GiB: the temporaries are a fraction of one
    assert mem.temp_size_in_bytes <= temp_gib * GIB
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= 15.75 * GIB, f"{total / GIB:.2f} GiB"
    assert cluster.transfer.resolved_write_back == TILES
    # one kernel a push, both of its fields aliased through it, booked
    # under ``apply`` (what `obs.costs.phase_map` credits its time to) ...
    kernels = re.findall(r" custom-call\(.*tpu_custom_call.*", text)
    assert len(kernels) == 2
    assert all('op_name="jit(step)/apply/rmw_tiles/' in k
               and "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}"
               in k for k in kernels)
    # ... and beside it the one row of the fields' last, partial tile
    # (2,340,001 = 1 mod 8), written in place where a push names it
    assert not re.findall(rf"= {field}\S* scatter\(", text)
    assert len(re.findall(
        rf"= {field}\S* dynamic-update-slice\(.*apply/", text)) == 4
    assert not re.findall(r" (?:while|conditional)\(", text)


@pytest.mark.parametrize("cell, rows, parent, owner_slots", [
    # 65,536 centers, a span of 73,344 positions: a chip renders 18,336
    # positions (110,016 target slots), an owner is sent up to 4 x 34,384
    # distinct target rows and 4 x 5,736 span rows
    ("gnews3m-x4-b64k", (393_216, 73_344), (8_448_998_912, 2_426_327_040),
     (137_536, 22_944)),
    # 16,384 centers, 18,432 positions: 4,608 a chip (27,648 target
    # slots), 4 x 8,640 and 4 x 1,448 an owner
    ("gnews3m-x4-b16k", (98_304, 18_432), (6_632_095_232, 609_871_872),
     (34_560, 5_792)),
])
def test_w2v_x4_step_routes_rows_to_their_owners(
        topo, no_compile_cache, tmp_path, monkeypatch, cell, rows, parent,
        owner_slots):
    """The table of the two four-chip cells is row-sharded over the
    ``model`` axis: the step runs split over it (ISSUE 43).  A chip holds
    nothing of the global batch's size — no row, gradient or index at the
    global target grid's or span's row count, no all-reduce of rows — what
    crosses chips is ``all-to-all``, every owner writes the rows at its
    head into its OWN shard (``tiles``, chosen from the shard's rows, as
    one chip would), no capacity-sized buffer beside the four aliased
    fields, and the step's peak and temporaries (``parent``: bytes a
    chip) are under the parent's (`92f1f4d`: three all-reduces of
    ``(global slots, 384)`` f32 and four field sweeps)."""
    cluster, model, compiled = _w2v_step(topo, tmp_path, monkeypatch, cell,
                                         chips=4)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert cluster.transfer.shards == 4 and model._step_split()
    assert cluster.transfer.resolved_write_back == TILES
    traffic = _cell(cell)[1]
    grid, span = rows
    assert model.stencil and model.span == span
    assert grid == traffic["centers_per_step"] * (model.negative + 1)
    # nothing at the global batch's row counts, whatever the type
    assert not re.findall(rf"\[(?:{grid}|{span})[\],]", text)
    # the only sums over the axis are scalars and a bound an owner
    reduced = re.findall(r"= (\S+) all-reduce(?:-start)?\(", text)
    assert reduced and all(re.match(r"\(?[fs]32\[4?\]", r) for r in reduced)
    assert len(re.findall(r" all-to-all\(", text)) >= 6
    assert not re.findall(r" (?:all-gather|reduce-scatter)(?:-start)?\(",
                          text)
    # the shard's fields: in and out aliased, never copied, and nothing
    # else of their size (a scatter-add into zeros, an accumulator)
    capacity = model.table.capacity // 4
    assert capacity == 975_001
    field = rf"f32\[{capacity},384\]"
    made = re.findall(rf"= {field}\S* ([\w\-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast",
                         "dynamic-update-slice"}
    assert not re.findall(rf"= {field}\S* (?:copy|broadcast|constant)\(",
                          text)
    assert _carried_aliased(text)
    # every write to a field is the tile kernel's, in place in the
    # owner's shard, on the slots the owner was sent — and one row of the
    # shard's last, partial tile (975,001 = 1 mod 8)
    assert not re.findall(rf"= {field}\S* (?:fusion|scatter)\(", text)
    kernels = re.findall(r" custom-call\(.*tpu_custom_call.*", text)
    assert [int(n) for k in kernels for n in re.findall(
        r"operand_layout_constraints=\{s32\[1\]\{0\}, s32\[\d+\]\{0\}, "
        r"f32\[\d+\]\{0\}, f32\[(\d+),384\]", k)] == sorted(owner_slots)
    assert all("/apply/rmw_tiles/" in k for k in kernels)
    assert not re.findall(r" conditional\(", text)
    assert (mem.peak_memory_in_bytes, mem.temp_size_in_bytes) < parent
    assert mem.temp_size_in_bytes < parent[1]


def test_table_is_built_within_its_own_size(topo, no_compile_cache,
                                            monkeypatch):
    """The program that draws word2vec's table, compiled for one v5e chip
    at 2,340,001 rows: the four 300-wide fields come out on 384 lanes,
    row-major by the compiler's own default, and building them needs next
    to nothing beside them (13.41 of 15.75 GiB: the draw is laid out and
    padded through buffers the zero fields will occupy)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from swiftmpi_tpu.cluster.mesh import MODEL_AXIS, MeshSpec, build_mesh
    from swiftmpi_tpu.parameter import sparse_table, w2v_access
    from swiftmpi_tpu.parameter.key_index import KeyIndex

    rows = 2_340_001
    mesh = build_mesh(MeshSpec.from_dict({"data": -1, "model": 1}),
                      devices=list(topo.devices)[:1])
    monkeypatch.setattr(sparse_table.SparseTable, "_init_state",
                        _shapes_only)
    table = sparse_table.SparseTable(w2v_access(0.7, 300), KeyIndex(1, rows),
                                     mesh=mesh, axis=MODEL_AXIS)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = table._init_program().lower(jax.ShapeDtypeStruct(
        key.shape, key.dtype, sharding=NamedSharding(mesh, P()))).compile()
    results = re.search(r"entry_computation_layout=\{(.*)",
                        compiled.as_text()).group(1).split(")->(")[1]
    assert results.count(f"f32[{rows},384]{{1,0:T(8,128)}}") == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 0.1 * GIB
    assert (mem.output_size_in_bytes + mem.temp_size_in_bytes
            <= 13.5 * GIB)


@pytest.mark.parametrize("width, row_major", [(300, False), (100, False),
                                              (384, True), (128, True)])
def test_default_layout_of_a_tall_field(topo, no_compile_cache, width,
                                        row_major):
    """What the chip's compiler stores a tall ``(rows, width)`` f32 array
    as when nobody says: column-major unless the width is a whole number
    of 128-lane tiles.  A read-modify-write of rows then copies the whole
    array into a row-major buffer and back (300, 100 wide: 2 copies, a
    temporary of the padded array) or touches the rows alone (384, 128
    wide: none).  This is the fact S1 rests on: a field whose STORED row is
    a multiple of 128 needs no layout of its own (ROADMAP D0)."""
    from jax.sharding import SingleDeviceSharding

    rows, batch = 2_340_001, 5_500
    one = SingleDeviceSharding(topo.devices[0])

    def rmw(x, idx, g):
        return x.at[idx].set(jnp.take(x, idx, axis=0) + g, mode="drop",
                             unique_indices=True)

    compiled = jax.jit(rmw, donate_argnums=0).lower(
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((batch, width), jnp.float32,
                             sharding=one)).compile()
    text = compiled.as_text()
    order = "1,0" if row_major else "0,1"
    assert f"(f32[{rows},{width}]{{{order}:T(8,128)}}" in text.split("\n")[0]
    copies = re.findall(rf"= f32\[{rows},{width}\]\S* copy\(", text)
    assert len(copies) == (0 if row_major else 2)
    padded = rows * (-(-width // 128) * 128) * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert (temp < 0.01 * GIB) if row_major else (temp >= padded)


def _backward_keeps_the_query_side_still(compiled, cfg, seqs, positions,
                                         parent_peak_gib):
    """Blockwise attention's backward sums a query tile's ``dq`` in its
    fold's carry (PR 39): the compiled step holds no
    ``dynamic-update-slice`` — bare, or the root of a fusion's computation —
    into an f32 buffer of the query's ``(B, S, Hkv, G, D)`` (the parent
    wrote a strided 16.8 MB tile of one back every fold: 1, 1 and 4 such
    instructions in the three LM steps), and the step's peak is not above
    the parent's.  The peak is the compiler's ``peak_memory_in_bytes``: it
    moved with what the chip reserved for the step (``sdar-ep8-8k-t16k``:
    7,482 -> 7,460 MB of ``peak_bytes_reserved``, PERF.md section 6) where
    ``temp_size_in_bytes`` did not (10.118 -> 10.158 GiB there; 9.411 ->
    9.407 and 6.524 -> 6.367 in the other two)."""
    q = (f"f32[{seqs},{positions},{cfg.kv_heads},"
         f"{cfg.n_heads // cfg.kv_heads},{cfg.head_dim}]")
    text = compiled.as_text()
    assert " dynamic-update-slice(" in text
    assert not re.findall(rf"= {re.escape(q)}\S* dynamic-update-slice\(",
                          text), q
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= parent_peak_gib * GIB, f"{peak / GIB:.4f} GiB"


def test_lfm2_ep4_trainer_step_fits_one_chip(topo, no_compile_cache):
    """The real ``trainer_step`` of ``lfm2-ep4-8k-t32k`` — 5 layers at the
    published widths, 8 of 32 experts, 4 packed sequences of 8,192 — on one
    v5e chip: the compiler's memory report fits 15.75 GiB, and no buffer has
    the size of a head's ``(S, S)`` scores or of a ``(T, E, C)`` dispatch.
    Peak 13.4366 GiB (the parent of PR 39: 13.4366, 1 KiB less)."""
    import sys
    sys.path.insert(0, REPO)
    from jax.sharding import SingleDeviceSharding

    from benchmark.families import lm as family
    from swiftmpi_tpu.models.trainer import Trainer

    config, traffic = _cell("lfm2-ep4-8k-t32k")
    cfg = family.transformer_config(config, traffic)
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    seqs, S = int(traffic["sequences_per_step"]), cfg.max_seq
    tokens = jax.ShapeDtypeStruct((seqs, S), jnp.int32, sharding=one)
    n_params = sum(a.size for a in jax.tree.leaves(state["params"]))
    assert n_params == 507_820_288            # ISSUE 31's count, 8.13 GB x 16 B

    compiled = trainer._build_step().lower(
        state["params"], state["opt_state"], state["step"], tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= 15.75 * GIB, f"{total / GIB:.2f} GiB"
    # the state is donated: every parameter and moment updated in place
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes

    text = compiled.as_text()
    # the step is its program at `a87c672`, instruction for instruction
    # (PR 49 gave `blockwise_attention`'s mask contract a data operand and
    # its `custom_vjp` a second output; a positional mask lowers as before)
    assert _instructions(text) == (9004, "101eb67644aa170c")
    assert "ragged-dot" in text               # the compiler's grouped matmul
    _backward_keeps_the_query_side_still(compiled, cfg, seqs, S, 13.437)
    # a head's (S, S) scores, or a (T, E, C) dispatch at C = T k / E x 2 =
    # 8,192, would be an array with two dims of at least S; the largest
    # things here have one (tokens x a width)
    for dims in set(re.findall(r"= \w+\[([\d,]+)\]", text)):
        big = [int(d) for d in dims.split(",") if int(d) >= S]
        assert len(big) < 2, f"[{dims}]"


def test_sdar_ep8_trainer_step_fits_one_chip(topo, no_compile_cache):
    """The real ``trainer_step`` of ``sdar-ep8-8k-t16k`` — 4 layers at the
    published widths (32 heads of 128 over a 2,048 residual), 16 of 128
    experts, an untied head, 2 packed sequences of 8,192 = 32,768 trunk
    positions of ``[x_t ; x_0]`` — on one v5e chip: the compiler's memory
    report fits 15.75 GiB, the grouped products are the compiler's one
    ``ragged-dot`` kernel family, and no buffer has the size of a head's
    scores over a whole sequence, noised + clean or either half.
    Peak 12.008 GiB (the parent of PR 39: 12.010)."""
    import sys
    sys.path.insert(0, REPO)
    from jax.sharding import SingleDeviceSharding

    from benchmark.families import bdlm as family
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    config, traffic = _cell("sdar-ep8-8k-t16k")
    cfg = family.transformer_config(config, traffic)
    assert (cfg.objective, cfg.head_dim, cfg.tied_head) == \
        ("block_diffusion", 128, False)
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    seqs, S = int(traffic["sequences_per_step"]), cfg.max_seq // 2
    tokens = jax.ShapeDtypeStruct((seqs, S), jnp.int32, sharding=one)
    n_params = sum(a.size for a in jax.tree.leaves(state["params"]))
    assert n_params == 456_346_624            # ISSUE 33's count, 7.30 GB x 16 B

    compiled = trainer._build_step().lower(
        state["params"], state["opt_state"], state["step"], tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= 15.75 * GIB, f"{total / GIB:.2f} GiB"
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes

    text = compiled.as_text()
    # the step is its program at `a87c672`, instruction for instruction
    # (PR 49 gave `blockwise_attention`'s mask contract a data operand and
    # its `custom_vjp` a second output; a positional mask lowers as before)
    assert _instructions(text) == (6836, "3235fd1e4d452f7e")
    # every kernel the compiler brings is a ragged-dot one, under the names
    # the catalog books as `experts` and `^ragged-dot` matches: 3 products
    # forward and 9 backward in the one scanned layer body
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"', text)
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert set(kernels) <= set(DEVICE_SCOPES)
    assert kernels.count("ragged-dot-none") == 12
    _backward_keeps_the_query_side_still(compiled, cfg, seqs, 2 * S, 12.010)
    # (2S, 2S) or (S, S) scores of a head would be an array with two dims
    # of at least S; the largest things here have one (positions x a width)
    for dims in set(re.findall(r"= \w+\[([\d,]+)\]", text)):
        big = [int(d) for d in dims.split(",") if int(d) >= S]
        assert len(big) < 2, f"[{dims}]"


def test_trinity_ep16_trainer_step_fits_one_chip(topo, no_compile_cache):
    """The real ``trainer_step`` of ``trinity-ep16-16k-t16k`` — 5 layers in
    four scanned runs at the published widths, sliding layers under
    ``WindowMask(2048)``, 8 of 128 experts beside the shared one, an untied
    25,024-row head, one packed sequence of 16,384 — on one v5e chip: the
    compiler's memory report fits 15.75 GiB with room (12.2 GiB), the
    grouped products are the compiler's ``ragged-dot`` kernels — 15 a layer
    body, the residual's second norm making the layer's recomputation run
    the expert loop again (``benchmark/costs/swlm.py::RAGGED_FORWARD_RUNS``)
    — and no buffer has the size of a head's ``(S, S)`` scores.
    Peak 10.506 GiB (the parent of PR 39: 10.662)."""
    import sys
    sys.path.insert(0, REPO)
    from jax.sharding import SingleDeviceSharding

    from benchmark.families import swlm as family
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    config, traffic = _cell("trinity-ep16-16k-t16k")
    cfg = family.transformer_config(config, traffic)
    assert [k for k, _n in cfg.layer_groups()] == [
        ("sliding", "dense"), ("sliding", "moe"), ("full", "moe"),
        ("sliding", "moe")]
    assert (cfg.window, cfg.head_dim, cfg.held[1] - cfg.held[0]) == \
        (2048, 128, 8)
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    seqs, S = int(traffic["sequences_per_step"]), cfg.max_seq
    tokens = jax.ShapeDtypeStruct((seqs, S), jnp.int32, sharding=one)
    n_params = sum(a.size for a in jax.tree.leaves(state["params"]))
    # attention 27,263,232 + four gains 8,192 a layer; dense MLP 37,748,736;
    # router 262,144 + bias 128 + shared 6,291,456 + 8 x 6,291,456; embedding
    # and head 2 x 51,249,152; final gain 2,048: 8.07 GB x 16 B
    assert n_params == 504_147_712

    compiled = trainer._build_step().lower(
        state["params"], state["opt_state"], state["step"], tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= 13.0 * GIB, f"{total / GIB:.2f} GiB"
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes

    text = compiled.as_text()
    # the step is its program at `a87c672`, instruction for instruction
    # (PR 49 gave `blockwise_attention`'s mask contract a data operand and
    # its `custom_vjp` a second output; a positional mask lowers as before)
    assert _instructions(text) == (21591, "0c870f916dddfcfb")
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"', text)
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert set(kernels) <= set(DEVICE_SCOPES)
    assert kernels.count("ragged-dot-none") == 3 * 15
    _backward_keeps_the_query_side_still(compiled, cfg, seqs, S, 10.663)
    for dims in set(re.findall(r"= \w+\[([\d,]+)\]", text)):
        big = [int(d) for d in dims.split(",") if int(d) >= S]
        assert len(big) < 2, f"[{dims}]"


def test_glm47f_ep8_trainer_step_fits_one_chip(topo, no_compile_cache):
    """The real ``trainer_step`` of ``glm47f-ep8-8k-t8k`` — a dense layer and
    a scanned run of four expert layers at the published widths, every
    attention layer latent (ranks 768 / 512, 20 heads of 192 + 64 over values
    of 256, ``G`` = 1), 8 of 64 experts beside the shared one, the
    multi-token-prediction module behind the trunk, an untied 19,360-row head
    run twice, one packed sequence of 8,192 — on one v5e chip: 706,518,848
    parameters; the compiler's ``peak_memory_in_bytes`` (what the chip must
    hold at once: 13.77 GiB) fits the 15.75 GiB the runtime gives, where the
    sum of arguments and every temporary allocation (16.62 GiB) would not;
    the grouped products are the compiler's ``ragged-dot`` kernels, 12 a
    layer body in two bodies (the stack's scanned one and the module's); the
    backward writes no strided ``dq`` tile; no buffer has the size of a
    head's ``(S, S)`` scores; and the module's instructions carry its own
    scopes."""
    import sys
    sys.path.insert(0, REPO)
    from jax.sharding import SingleDeviceSharding

    from benchmark.costs import mlalm as costs
    from benchmark.families import mlalm as family
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    config, traffic = _cell("glm47f-ep8-8k-t8k")
    cfg = family.transformer_config(config, traffic)
    assert cfg.layer_groups() == [(("latent", "dense"), 1),
                                  (("latent", "moe"), 4)]
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (768, 512, 20, 192, 64, 256)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held[1] - cfg.held[0],
            cfg.mtp_layers) == (64, 4, 8, 1)
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    seqs, S = int(traffic["sequences_per_step"]), cfg.max_seq
    assert (seqs, S) == (1, 8192)
    tokens = jax.ShapeDtypeStruct((seqs, S), jnp.int32, sharding=one)
    n_params = sum(a.size for a in jax.tree.leaves(state["params"]))
    # latent attention 21,759,232 + two gains 4,096 a layer; dense FFN
    # 62,914,560; router 131,072 + bias 64 + shared 9,437,184 + 8 x
    # 9,437,184; embedding and head 2 x 39,649,280; final gain 2,048; the
    # module 8,388,608 + three gains 6,144 + one expert layer: 11.30 GB x 16 B
    assert n_params == 706_518_848
    shape = {"kinds": [("latent", "dense")] + [("latent", "moe")] * 4,
             "mtp": 1, "d_model": 2048, "heads": 20, "q_rank": 768,
             "kv_rank": 512, "nope": 192, "rope": 64, "v_dim": 256,
             "d_ff": 10240, "d_expert": 1536, "d_shared": 1536,
             "experts": 64, "experts_held": 8, "vocab": 19360}
    assert costs.parameters(shape) == n_params

    compiled = trainer._build_step().lower(
        state["params"], state["opt_state"], state["step"], tokens).compile()
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes <= 14.0 * GIB, \
        f"{mem.peak_memory_in_bytes / GIB:.3f} GiB"
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes
    assert mem.argument_size_in_bytes <= 7.9 * GIB       # 12 B a parameter

    text = compiled.as_text()
    # the step is its program at `a87c672`, instruction for instruction
    # (PR 49 gave `blockwise_attention`'s mask contract a data operand and
    # its `custom_vjp` a second output; a positional mask lowers as before)
    assert _instructions(text) == (15269, "8d7de46ce30c12ba")
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"', text)
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert kernels.count("ragged-dot-none") == 2 * 12
    assert " dynamic-update-slice(" in text
    assert not re.findall(
        rf"= f32\[{seqs},{S},20,1,256\]\S* dynamic-update-slice\(", text)
    # (widths reach past S here: 8,960 = 20 x 448, 10,240, 19,360)
    for dims in set(re.findall(r"= \w+\[([\d,]+)\]", text)):
        assert dims.split(",").count(str(S)) < 2, f"[{dims}]"
    scopes = set(re.findall(r"[/(](mtp_\w+|mtp|latent_attention)[/)]", text))
    assert {"mtp", "mtp_latent_attention", "mtp_route", "mtp_experts",
            "mtp_shared_expert", "latent_attention"} <= scopes
    assert scopes <= set(DEVICE_SCOPES)


def test_nemotron3n_ep16_trainer_step_fits_one_chip(topo, no_compile_cache):
    """The real ``trainer_step`` of ``nemotron3n-ep16-8k-t8k`` — nine runs of
    one half layer each at the published widths (four Mamba-2 mixers of 64
    heads of 64 with a 128-wide state and 8 groups, one attention layer of 32
    query heads on 2 KV heads of 128, four expert layers of 8 of 128
    ungated squared-ReLU experts beside a 3,712-wide shared one), an untied
    16,384-row head, one packed sequence of 8,192 — on one v5e chip:
    666,963,456 parameters; the compiler's ``peak_memory_in_bytes`` (11.55
    GiB) fits the 15.75 GiB the runtime gives; the grouped products are the
    compiler's ``ragged-dot`` kernels, 8 a layer body (two products an
    expert: 2 forward + 6 backward); the scan keeps chunk states and the
    chunks' ``(128, 128)`` forms, never a position's state; no buffer has
    the size of a head's ``(S, S)`` scores; and the mixers' instructions
    carry their two scopes."""
    import sys
    sys.path.insert(0, REPO)
    from jax.sharding import SingleDeviceSharding

    from benchmark.costs import sslm as costs
    from benchmark.families import sslm as family
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    config, traffic = _cell("nemotron3n-ep16-8k-t8k")
    cfg = family.transformer_config(config, traffic)
    kinds = [("ssm", "none"), ("none", "moe")] * 2 + [
        ("ssm", "none"), ("full", "none"), ("none", "moe"), ("ssm", "none"),
        ("none", "moe")]
    assert cfg.layer_groups() == [(kind, 1) for kind in kinds]
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held[1] - cfg.held[0],
            cfg.d_expert, cfg.shared_width, cfg.expert_act) == \
        (128, 6, 8, 1856, 3712, "relu2")
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    seqs, S = int(traffic["sequences_per_step"]), cfg.max_seq
    assert (seqs, S) == (1, 8192)
    tokens = jax.ShapeDtypeStruct((seqs, S), jnp.int32, sharding=one)
    n_params = sum(a.size for a in jax.tree.leaves(state["params"]))
    # a mixer 38,744,896; an expert layer 100,125,440; the attention layer
    # 23,399,040; embedding and head 2 x 44,040,192; final gain 2,688:
    # 10.67 GB x 16 B
    assert n_params == 666_963_456
    shape = {"kinds": kinds, "d_model": 2688, "heads": 32, "kv_heads": 2,
             "d_head": 128, "ssm_heads": 64, "ssm_head_dim": 64,
             "ssm_state": 128, "ssm_groups": 8, "kernel": 4,
             "d_expert": 1856, "d_shared": 3712, "experts": 128,
             "experts_held": 8, "vocab": 16384}
    assert costs.parameters(shape) == n_params

    compiled = trainer._build_step().lower(
        state["params"], state["opt_state"], state["step"], tokens).compile()
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes <= 11.8 * GIB, \
        f"{mem.peak_memory_in_bytes / GIB:.3f} GiB"
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes
    assert mem.argument_size_in_bytes <= 7.5 * GIB       # 12 B a parameter

    text = compiled.as_text()
    # the step is its program at `a87c672`, instruction for instruction
    # (PR 49 gave `blockwise_attention`'s mask contract a data operand and
    # its `custom_vjp` a second output; a positional mask lowers as before)
    assert _instructions(text) == (21049, "9095011c6e5d4ffe")
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"', text)
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert kernels.count("ragged-dot-none") == 4 * 8
    # the scan: 64 chunk states of (8 groups x 8 heads, 64, 128) a sequence
    # are there, a state a position is not, and neither is a (S, S) form
    assert re.search(r"= f32\[64,1,8,8,64,128\]", text)
    for dims in set(re.findall(r"= \w+\[([\d,]+)\]", text)):
        sizes = [int(d) for d in dims.split(",")]
        assert sizes.count(S) < 2, f"[{dims}]"
        assert not (S in sizes and {64, 128} <= set(sizes)
                    and math.prod(sizes) >= S * 64 * 64 * 128), f"[{dims}]"
    scopes = set(re.findall(r"[/(](ssm_\w+)(?=[/)])", text))
    assert scopes == {"ssm_mixer", "ssm_scan"} and scopes <= set(DEVICE_SCOPES)


def _push_access(width, dtype):
    """AdaGrad on one ``(param, accumulator)`` pair stored ``width`` lanes
    wide, as given (no padding to whole tiles)."""
    from swiftmpi_tpu.parameter.access import (AdaGradAccess, AdaGradRule,
                                               FieldSpec)
    return AdaGradAccess(
        0.3, rules=(AdaGradRule("h", "h2sum", "h"),),
        fields={"h": FieldSpec(width, dtype=dtype), "h2sum": FieldSpec(width)},
        pull_fields=("h",))


@pytest.mark.parametrize("width, dtype, shards, platform, slots, form, "
                         "program", [
    # rows of whole 128-lane f32 tiles on one TPU: the kernel's
    (384, jnp.float32, 1, "tpu", 20_480, "tiles", None),
    # ... unless the push is so long that one sweep of the fields is cheaper
    (384, jnp.float32, 1, "tpu", 700_000, "sweep", None),
    # every other push keeps the parent's program (`358056b`), by digest:
    # a width that stays column-major, a half-width parameter, a table
    # split by the partitioner, a backend told its devices are no TPUs,
    # one-wide logistic rows
    (300, jnp.float32, 1, "tpu", 20_480, "per_row", (370, "ad7b519fc1b758c5")),
    (384, jnp.bfloat16, 1, "tpu", 5_000, "per_row", (329, "7fc2be6cb32cf36f")),
    (384, jnp.float32, 4, "tpu", 163_840, "sweep", (376, "82f00e0ee6496e2d")),
    (384, jnp.float32, 1, "cpu", 5_000, "per_row", (325, "0bc0f70a87c3f24b")),
    (1, jnp.float32, 1, "tpu", 1_000, "sweep", (337, "9d96a2859728e5b4")),
])
def test_push_outside_the_kernel_s_predicate_compiles_as_before(
        topo, no_compile_cache, width, dtype, shards, platform, slots, form,
        program):
    """`XlaTransfer.write_back_form` hands the tile kernel the pushes its
    predicate names (dtype, width, platform, the rows held) and no other:
    what it does not take lowers to the parent's text."""
    from jax.sharding import SingleDeviceSharding

    from swiftmpi_tpu.transfer.xla import XlaTransfer

    one = SingleDeviceSharding(topo.devices[0])
    access = _push_access(width, dtype)
    backend = XlaTransfer(dense_apply=False, shards=shards, platform=platform)
    rows = 2_340_001

    def push(state, idx, g):
        return backend.push(state, idx, {"h": g}, access, mean=True)

    compiled = jax.jit(push, donate_argnums=0).lower(
        {f: jax.ShapeDtypeStruct((rows, width), spec.dtype, sharding=one)
         for f, spec in access.fields.items()},
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((slots, width), jnp.float32,
                             sharding=one)).compile()
    text = compiled.as_text()
    assert backend.resolved_write_back == dict.fromkeys(("h", "h2sum"), form)
    assert ("tpu_custom_call" in text) == (form == "tiles")
    if program is not None:
        assert _instructions(text) == program
