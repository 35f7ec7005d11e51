"""Real-size programs compiled for a v5e chip that is described, not
attached (on-chip-measurement guide, section 2): costs no chip time, says
nothing about results or times.

The topology is described inside a fixture so that every worker collects
the same tests and only the one given this file loads the TPU's compiler.
The LM cells' steps are in ``tests/test_compile_v5e_lm.py`` (and
``keye2``'s in ``tests/test_sparse_attention.py``) with this file's
fixtures: tier-1's command lets several processes load the compiler
(``ALLOW_MULTIPLE_LIBTPU_LOAD=1``), and one file of all of them was the
longest of the suite by a half (PR 50).
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
