"""The sparse push's row write-back (``transfer/xla.py``): every form
writes the ``local`` oracle's rows, bit for bit.

``XlaTransfer.write_back_form`` chooses, from static shapes, between
writing a push's rows one by one (``per_row``) and one sweep of the whole
field (``sweep``) and, on the fields it can (f32 rows of whole 128-lane
tiles, one TPU), moving the 8-row tiles of the distinct rows at the head
of the push through one Pallas kernel (``tiles``, PR 47;
``transfer/tile_rmw.py``): a choice of device time (PERF.md section 6,
PR 30, PR 34, PR 47), never of values.  Every case here runs the push in
EVERY form, twice: op by op, where the access rule's arithmetic is the
oracle's own sequence of primitives and every field must equal the numpy
oracle's bit for bit (``assert_array_equal``); and under ``jit``, as a
train step runs it, where the forms must equal each other bit for bit
and stay within one rounding of the oracle (XLA fuses the rule's
arithmetic there; so it does op by op in ``tiles``, whose kernel body is
one compiled program: held there to its own jitted run, bit for bit, a
mean push's within one rounding).  The kernel
runs in Pallas' TPU interpret mode here: its copies, semaphores and
control flow as written, the chip's compiler aside
(``tests/test_compile_v5e.py`` has that).  On the row-sharded table it
runs where the program runs it there: on each owner's shard, the rows
routed to their owners by a backend told of the mesh (the other forms go
through the partitioner, as before).  The gradients are multiples of
1/64 and every slot repeats 1, 2 or 4 times, so the oracle's ``sum /
count`` and the backend's ``sum * (1 / count)`` are the same float.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from swiftmpi_tpu.cluster import SHARD_AXIS, ps_mesh
from swiftmpi_tpu.parameter import KeyIndex, SparseTable, lr_access, w2v_access
from swiftmpi_tpu.transfer.local import LocalTransfer
from swiftmpi_tpu.transfer.xla import XlaTransfer

FORMS = ("per_row", "sweep")
SHARDS = 4
#: 25 rows: the last one's tile reaches past the fields (`_rmw_tiles`
#: writes it row by row)
CAP_PER_SHARD = 25
#: slots a grid step of the kernel here, and its ring's slots: the 12
#: distinct rows of a batch are two steps, the ring is taken twice over
BLOCK, DEPTH = 8, 4


def _batch(case, table, width, seed):
    """(slots, grads) of one push family over ``table``'s rows."""
    rng = np.random.default_rng(seed)
    rows = rng.permutation(table.capacity)[:12].astype(np.int32)
    if case == "duplicates":          # each row 1, 2 or 4 times, shuffled
        slots = np.concatenate([rows[:4], np.repeat(rows[4:8], 2),
                                np.repeat(rows[8:], 4)])
    elif case == "padding_mixed":     # the same, padding interleaved
        slots = np.concatenate([rows[:4], np.repeat(rows[4:8], 2),
                                np.repeat(rows[8:], 4)])
        slots = np.insert(slots, np.arange(0, len(slots), 2), -1)
    elif case == "all_padding":
        slots = np.full(16, -1, np.int32)
    else:
        assert case == "empty"
        slots = np.zeros(0, np.int32)
    slots = rng.permutation(slots).astype(np.int32)
    grad = (rng.integers(-64, 65, size=(len(slots), width)) / 64.0
            ).astype(np.float32)
    return slots, grad


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_device", "row_sharded_x4"])
@pytest.mark.parametrize("mean", [False, True], ids=["sum", "mean"])
@pytest.mark.parametrize("width", [1, 300, 128, 384])
@pytest.mark.parametrize("case", ["duplicates", "padding_mixed",
                                  "all_padding", "empty"])
def test_write_back_forms_match_oracle_bitwise(case, width, mean, sharded,
                                               monkeypatch):
    from swiftmpi_tpu.transfer import tile_rmw

    if sharded and len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} virtual devices")
    mesh = ps_mesh(n=SHARDS) if sharded else None
    monkeypatch.setattr(tile_rmw, "BLOCK", BLOCK)
    monkeypatch.setattr(tile_rmw, "DEPTH", DEPTH)
    # d = 1: the logistic table; else word2vec's, pushing the ``h``
    # family alone, so ``v`` / ``v2sum`` must come back untouched
    access = lr_access(0.3) if width == 1 else w2v_access(0.3, width)
    family = "val" if width == 1 else "h"
    ki = KeyIndex(num_shards=SHARDS if sharded else 1,
                  capacity_per_shard=CAP_PER_SHARD)
    table = SparseTable(access, ki, mesh=mesh,
                        axis=SHARD_AXIS if sharded else "model")
    # rows are pushed at their STORED width (300 -> 384 lanes, zeros
    # beyond the vector: `access.stored_width`)
    stored = table.state[family].shape[1]
    assert stored == {1: 1, 300: 384, 128: 128, 384: 384}[width]
    # widths whose stored row is whole 128-lane tiles also take the
    # kernel, on the rows one device holds: the whole table, or — the
    # rows routed to their owners, as a backend told of the mesh does —
    # an owner's shard
    forms = FORMS + (("tiles",) if stored % 128 == 0 else ())
    slots, grad = _batch(case, table, stored, seed=width + 7 * mean)
    state_np = {f: np.asarray(v) for f, v in table.state.items()}
    want = LocalTransfer().push(state_np, slots, {family: grad}, access,
                                mean=mean)

    def push(backend, state, slots, grad):
        return backend.push(state, slots, {family: grad}, access, mean=mean)

    eager, jitted = {}, {}
    for form in forms:
        backend = XlaTransfer(
            dense_apply=False, shards=SHARDS if sharded else 1,
            **(dict(mesh=mesh, axis=SHARD_AXIS)
               if sharded and form == "tiles" else {}))
        monkeypatch.setattr(backend, "write_back_form",
                            lambda n, fields, form=form: form)
        with pltpu.force_tpu_interpret_mode():
            out = push(backend, table.state, jnp.asarray(slots),
                       jnp.asarray(grad))
            eager[form] = {f: np.asarray(v) for f, v in out.items()}
            if len(slots):
                touched = access.touched_fields([family])
                assert backend.resolved_write_back == dict.fromkeys(touched,
                                                                    form)
            out = jax.jit(push, static_argnums=0)(
                backend, table.state, jnp.asarray(slots), jnp.asarray(grad))
        if sharded:
            assert out[family].sharding.is_equivalent_to(
                table.state[family].sharding, 2)
        jitted[form] = {f: np.asarray(v) for f, v in out.items()}
        for f in access.fields:
            if form == "tiles":
                # one compiled program either way, fused as the compiler
                # likes: held to the jitted one, as that is to the oracle
                # — bit for bit, but for a mean push: jitted, interpret
                # mode's kernel is inlined into the program that computes
                # ``1 / count``, and the CPU's compiler rounds their
                # product as it likes (one ulp in two of the 16 cases)
                np.testing.assert_allclose(jitted[form][f], eager[form][f],
                                           rtol=2e-7 if mean else 0, atol=0,
                                           err_msg=f"{form}:{f}")
            else:
                np.testing.assert_array_equal(want[f], eager[form][f],
                                              err_msg=f"{form}:{f}")
            np.testing.assert_allclose(want[f], jitted[form][f],
                                       rtol=1e-6, atol=1e-7,
                                       err_msg=f"jit {form}:{f}")
    for form in forms:
        for f in access.fields:
            np.testing.assert_array_equal(jitted["sweep"][f], jitted[form][f],
                                          err_msg=f"forms {form}:{f}")
    if case in ("all_padding", "empty"):
        for f in access.fields:
            np.testing.assert_array_equal(state_np[f], jitted["sweep"][f])


# slots of a push, the field it writes (a 300-wide row is stored on 384
# lanes), the table's shards, the devices' platform -> the form.  The
# first seven are the benchmark cells' own pushes (PERF.md section 4): on
# the CPU the two forms the shapes choose between, on a TPU the head's
# rows wherever the field is one `write_back_form` gives them for.
CELLS = [
    (5_000, 2_340_001, 384, 1, "per_row"),       # cbow2m-demo, contexts
    (5_500, 2_340_001, 384, 1, "per_row"),       # cbow2m-demo, targets
    (20_480, 2_340_001, 384, 1, "per_row"),      # sg2m-b2k, inputs
    (122_880, 2_340_001, 384, 1, "sweep"),       # sg2m-b2k, targets
    (163_840, 2_340_001, 384, 1, "sweep"),       # cbow2m-b16k, the pair grid
    (22_400, 2_340_001, 384, 1, "per_row"),      # ... its span (PR 36)
    (655_360, 3_900_004, 384, 4, "sweep"),       # gnews3m-x4-b64k
]


@pytest.mark.parametrize("n, rows, width, shards, platform, form", [
    *((*c[:4], "cpu", c[4]) for c in CELLS),
    (100_000, 3_900_004, 384, 1, "cpu", "per_row"),  # the same rows, unsharded
    (100_000, 3_900_004, 384, 4, "cpu", "sweep"),    # ... a shard is a quarter
    (1_000, 1 << 20, 1, 1, "cpu", "sweep"),          # d = 1: a cheap sweep
    (100, 1 << 20, 1, 1, "cpu", "per_row"),
    # one TPU, rows of whole 128-lane tiles: the head's tiles ...
    *((*c[:4], "tpu", "tiles") for c in CELLS[:6]),
    (180_224, 2_340_001, 384, 1, "tpu", "tiles"),    # cbow2m-b16k, targets
    (100_000, 3_900_004, 128, 1, "tpu", "tiles"),
    # ... an owner's shard of gnews3m-x4-b64k and its longest push too ...
    (137_536, 975_001, 384, 1, "tpu", "tiles"),
    # ... until the push names most tiles of the fields: one sweep then
    # (`_TILE_SLOT_AS_SWEPT_BYTES`: from 678,159 slots on a 3.59 GB field,
    # 282,567 on a 1.5 GB shard)
    (678_158, 2_340_001, 384, 1, "tpu", "tiles"),
    (678_159, 2_340_001, 384, 1, "tpu", "sweep"),
    (282_566, 975_001, 384, 1, "tpu", "tiles"),
    (282_567, 975_001, 384, 1, "tpu", "sweep"),
    # a TPU, and everything else keeps the shapes' answer: four shards,
    # 300 wide (column-major by default), one wide
    (655_360, 3_900_004, 384, 4, "tpu", "sweep"),
    (100_000, 3_900_004, 384, 4, "tpu", "sweep"),
    (20_480, 2_340_001, 300, 1, "tpu", "per_row"),
    (163_840, 2_340_001, 300, 1, "tpu", "sweep"),
    (1_000, 1 << 20, 1, 1, "tpu", "sweep"),
    (100, 1 << 20, 1, 1, "tpu", "per_row"),
])
def test_write_back_form_follows_the_shapes(n, rows, width, shards, platform,
                                            form):
    fields = [jax.ShapeDtypeStruct((rows, width), jnp.float32)] * 2
    backend = XlaTransfer(shards=shards, platform=platform)
    assert backend.write_back_form(n, fields) == form


def test_write_back_form_wants_f32_rows_and_names_its_platform():
    """A bf16 field keeps the shapes' answer on a TPU too, and a backend
    nobody told takes the platform of this process's devices."""
    rows = [jax.ShapeDtypeStruct((2_340_001, 384), jnp.bfloat16)] * 2
    assert XlaTransfer(platform="tpu").write_back_form(5_000, rows) \
        == "per_row"
    assert XlaTransfer().platform == jax.devices()[0].platform


@pytest.mark.parametrize("slots, ordered", [
    (768, False), (5_500, False),       # cbow2m-demo: 1.2 and 8.4 MB
    (10_922, False), (10_923, True),    # 16 MiB less a row, and with it
    (20_480, True), (22_400, True),     # sg2m-b2k's inputs, b16k's span
    (180_224, True),                    # b16k's targets: 277 MB
])
def test_tiles_push_is_ordered_behind_its_state_from_16_mib(slots, ordered):
    """Which ``tiles`` pushes `_push_rows` puts an `optimization_barrier`
    before (`_ORDERED_PUSH_BYTES`, by the gradients' bytes): the one-chip
    cells' but cbow2m-demo's, whose step hangs the v5e with it
    (``scripts/barrier_hang_repro.py``; PERF.md section 6, PR 47)."""
    access = w2v_access(0.3, 300)
    backend = XlaTransfer(dense_apply=False, platform="tpu")
    fields = {f: jax.ShapeDtypeStruct((2_340_001, 384), jnp.float32)
              for f in access.fields}
    jaxpr = jax.make_jaxpr(lambda state, idx, g: backend.push(
        state, idx, {"h": g}, access, mean=True))(
            fields, jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, 384), jnp.float32))
    assert set(backend.resolved_write_back.values()) == {"tiles"}
    barriers = [e for e in jaxpr.eqns
                if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == ordered
    if ordered:     # every field of the state and the gradients, no less
        assert [v.aval.shape for v in barriers[0].invars] == [
            (2_340_001, 384)] * 4 + [(slots, 384)]


@pytest.mark.parametrize("mean", [False, True])
def test_span_push_is_the_sparse_push_with_counts(monkeypatch, mean):
    """``push_span`` (position-ordered rows, each the sum of ``counts[i]``
    contributions) sorts its slots like any sparse push and writes the
    distinct rows back in the form the shapes give (PR 36; before it,
    always row by row from unsorted owner positions): the oracle's rows,
    ``mean`` dividing by the summed counts."""
    from swiftmpi_tpu.transfer import xla
    seen = []
    real = xla._set_rows
    monkeypatch.setattr(xla, "_set_rows", lambda field, rows, values, sweep:
                        seen.append(sweep) or real(field, rows, values,
                                                   sweep))
    access = lr_access(0.3)
    table = SparseTable(access, KeyIndex(num_shards=1,
                                         capacity_per_shard=CAP_PER_SHARD))
    slots = np.array([5, 3, 5, -1, 9, 3], np.int32)
    grads = {"val": np.arange(6, dtype=np.float32)[:, None] / 8}
    counts = np.array([2, 1, 3, 4, 1, 0], np.float32)
    state_np = {f: np.asarray(v) for f, v in table.state.items()}
    want = LocalTransfer().push_span(state_np, slots, grads, counts, access,
                                     mean=mean)
    backend = XlaTransfer()
    got = backend.push_span(table.state, slots, grads, counts, access,
                            mean=mean)
    form = backend.write_back_form(6, [table.state[f] for f in access.fields])
    assert form in ("per_row", "sweep")
    assert seen == [form == "sweep"] * 2          # val, grad2sum
    assert backend.resolved_write_back == dict.fromkeys(access.fields, form)
    for f in access.fields:
        np.testing.assert_allclose(want[f], np.asarray(got[f]), rtol=1e-6,
                                   atol=1e-7, err_msg=f)


def _tile_case(case, capacity, rng):
    """``(rows at the head, batch length)`` of a kernel case over
    ``capacity`` rows, `BLOCK` = 8 slots a grid step."""
    whole = capacity - capacity % 8
    if case.startswith("share_"):
        # k rows of one tile, a lone row either side of them
        k = int(case[6:])
        return np.concatenate([[3], 16 + np.sort(rng.permutation(8)[:k]),
                               [41]]), 16
    if case == "block_edge":
        # slots 6..9 lie in one tile: the run crosses the first step's end
        return np.array([0, 9, 17, 25, 33, 41, 48, 49, 52, 55, 57, 70]), 24
    if case == "n_0":
        return np.zeros(0, np.int64), 16
    if case == "n_B":
        return np.sort(rng.permutation(whole)[:16]), 16
    if case == "last_tile":
        # every row of the partial tile at the fields' end, behind rows of
        # whole ones: not the kernel's
        return np.concatenate([[2, 11, whole - 1],
                               np.arange(whole, capacity)]), 24
    if case == "only_last_tile":
        return np.arange(whole, capacity), 8
    if case.startswith("run_"):
        return _run_case(case[4:], whole, rng)
    return np.sort(rng.permutation(whole)[:11]), 16   # mean_inv, sgd


def _run_case(case, whole, rng):
    """Heads whose named tiles are adjacent (`tile_rmw.RUN` = C tiles a
    copy at most): ``(rows, batch length[, slots a grid step[, n[, tiles
    a copy at most]]])``."""
    from swiftmpi_tpu.transfer.tile_rmw import RUN as C

    def one_each(tiles):            # a row of every tile, any sublane
        tiles = np.asarray(tiles)
        return 8 * tiles + rng.integers(0, 8, len(tiles))
    lengths = {"1": 1, "2": 2, "C": C, "C+1": C + 1, "2C+1": 2 * C + 1}
    if case in lengths:
        # a lone tile, the run an un-named tile behind it, a lone tile an
        # un-named tile behind the run; one grid step
        k = lengths[case]
        return one_each([0, *range(2, 2 + k), 3 + k]), 32, 32
    if case == "block_edge":
        # a run of six tiles from slot 5 on: cut where the first step ends
        return one_each([0, 2, 4, 6, 8, *range(10, 16)]), 16
    if case == "to_last_whole":
        # the run ends in the last whole tile; the partial one behind it is
        # named too, and not the kernel's
        last = whole // 8 - 1
        return np.concatenate([one_each([0, last - 2, last - 1, last]),
                               np.arange(whole, whole + 1)]), 16, 16
    if case == "gap_of_one":
        return one_each([2, 3, 5, 6]), 8
    if case == "every_row":
        # every row of 2 C + 1 adjacent tiles, over several grid steps
        return np.arange(8, 8 * (2 * C + 2)), 8 * (2 * C + 2), 32
    if case == "n_0":
        # adjacent tiles behind an empty head: never read
        return one_each(range(2, 2 + C)), 16, 16, 0
    if case.startswith("ring_"):
        # more copies a grid step than the ring has slots, at 1, 2 and C
        # tiles a copy: a slot's write is awaited where it is taken again
        run = {"1": 1, "2": 2, "C": C}[case[5:]]
        return np.sort(rng.permutation(whole)[:60]), 64, 64, None, run
    assert case == "n_B", case
    # two rows of each of eight adjacent tiles: the batch is all head
    return 8 * np.repeat(np.arange(1, 9), 2) + np.concatenate(
        [np.sort(rng.permutation(8)[:2]) for _ in range(8)]), 16, 16


@pytest.mark.parametrize("width", [128, 384])
@pytest.mark.parametrize("case, capacity", [
    *((f"share_{k}", 96) for k in range(2, 9)), ("block_edge", 96),
    ("n_0", 96), ("n_B", 96), ("last_tile", 97), ("last_tile", 103),
    ("only_last_tile", 97), ("only_last_tile", 103), ("mean_inv", 96),
    ("sgd", 96),
    *((f"run_{k}", 320) for k in ("1", "2", "C", "C+1", "2C+1")),
    ("run_block_edge", 160), ("run_to_last_whole", 97),
    ("run_to_last_whole", 103), ("run_gap_of_one", 96),
    ("run_every_row", 320), ("run_n_0", 96), ("run_n_B", 96),
    *((f"run_ring_{k}", 4000) for k in ("1", "2", "C"))])
def test_tile_kernel_matches_the_row_by_row_write(case, capacity, width,
                                                  monkeypatch):
    """`_rmw_tiles` against `_rmw_rows` on the same ascending head: rows
    that share a tile (read once, written once), a run across a grid
    step's edge (the step drains its writes, the next reads the tile
    again), an empty head and a full one, rows in the partial tile at the
    fields' end (``capacity % 8`` 1 and 7: written row by row), a mean
    push's ``1 / count``, a rule without an accumulator; and named tiles
    that are adjacent, which one copy moves (`_run_case`): runs of 1, 2,
    C, C + 1 and 2 C + 1 tiles (C: `tile_rmw.RUN`), a run across a grid
    step's edge, one that ends in the last whole tile, two an un-named
    tile apart, every row of a long run, runs behind an empty head and in
    a head that fills its batch, the ring taken twice over in a grid step.
    Rows the push
    does not name come back bit for bit — the other seven of a named
    row's tile among them — and the named ones equal `_rmw_rows`' bit for
    bit, a mean push's too."""
    from swiftmpi_tpu.parameter.access import FieldSpec, SGDAccess, zeros_init
    from swiftmpi_tpu.transfer import tile_rmw, xla

    rng = np.random.default_rng(len(case) + capacity + width)
    made = _tile_case(case, capacity, rng)
    head, B, block, n, run = (
        *made, *(BLOCK, None, tile_rmw.RUN)[len(made) - 2:])
    monkeypatch.setattr(tile_rmw, "BLOCK", block)
    monkeypatch.setattr(tile_rmw, "RUN", run)
    monkeypatch.setattr(tile_rmw, "DEPTH", DEPTH)
    rows = np.full(B, capacity, np.int32)
    rows[:len(head)] = head
    if n is not None:       # the rows behind ``n`` stand where they are
        head = head[:n]
    if case == "sgd":
        access = SGDAccess(0.3, {"h": FieldSpec(width, zeros_init)}, ("h",),
                           ("h",))
        names = ("h",)
    else:
        access, names = w2v_access(0.3, width), ("h", "h2sum")
    fields = {f: jnp.asarray(rng.random((capacity, width)) + 0.5,
                             jnp.float32) for f in names}
    grads = {"h": jnp.asarray(rng.normal(size=(B, width)), jnp.float32)}
    inv = jnp.asarray(1.0 / rng.integers(1, 5, (B, 1)), jnp.float32) \
        if case == "mean_inv" else None
    n = jnp.int32(len(head))

    want = jax.jit(lambda *a: xla._rmw_rows(*a, access, sweep=False,
                                            inv=inv))(
        fields, np.where(np.arange(B) < len(head), rows, capacity), grads)
    with pltpu.force_tpu_interpret_mode():
        got, copies = jax.jit(lambda *a: xla._rmw_tiles(
            *a, access, n, inv=inv))(fields, rows, grads)
    whole = capacity - capacity % 8
    assert int(copies) == np.count_nonzero(copies_walked(
        rows, int(np.sum(rows[:len(head)] < whole)), min(block, B),
        tile_rmw.RUN))
    named = np.zeros(capacity, bool)
    named[head] = True
    for f in names:
        w, g = np.asarray(want[f]), np.asarray(got[f])
        np.testing.assert_array_equal(g[~named], np.asarray(fields[f])[~named],
                                      err_msg=f)
        np.testing.assert_array_equal(g, w, err_msg=f)
        if len(head):
            assert (g[named] != np.asarray(fields[f])[named]).any()


def copies_walked(rows, n, block, longest):
    """The copies the kernel makes one way a field for the head
    ``rows[:n]``, slot by slot: at the slot that opens a copy the tiles it
    moves, 0 elsewhere.  A named tile joins the copy in front while it is
    the next tile of the field, the copy has room (``longest`` tiles) and
    the grid step (``block`` slots) has not ended."""
    out = np.zeros(len(rows), np.int32)
    at = None
    for j in range(n):
        tile = rows[j] >> 3
        if j % block and tile == rows[j - 1] >> 3:
            continue
        if (j % block and tile == (rows[j - 1] >> 3) + 1
                and out[at] < longest):
            out[at] += 1
        else:
            at = j
            out[at] = 1
    return out


@pytest.mark.parametrize("n, B, capacity", [
    (0, 16, 96), (16, 16, 96), (1, 40, 96), (40, 40, 400), (300, 320, 800),
    (200, 256, 4000), (250, 256, 256)])
def test_tile_kernel_counts_the_copies_a_walk_of_the_head_makes(n, B, capacity,
                                                                monkeypatch):
    """The kernel's own count of the copies it starts one way a field
    (``tile_copies_per_step`` sums it) against `copies_walked` on heads of
    every density, over several grid steps; as many tiles as copies moved
    them, a tile two grid steps share counted in both."""
    from swiftmpi_tpu.transfer import tile_rmw

    monkeypatch.setattr(tile_rmw, "BLOCK", 64)
    rng = np.random.default_rng(n + capacity)
    rows = np.full(B, capacity, np.int32)
    rows[:n] = np.sort(rng.permutation(capacity)[:n])
    access = w2v_access(0.3, 128)
    fields = {f: jnp.asarray(rng.random((capacity, 128)) + 0.5, jnp.float32)
              for f in ("h", "h2sum")}
    grads = {"h": jnp.asarray(rng.normal(size=(B, 128)), jnp.float32)}
    with pltpu.force_tpu_interpret_mode():
        _, copies = jax.jit(lambda *a: tile_rmw.rmw_tiles(
            *a, access, jnp.int32(n)))(fields, rows, grads)
    want = copies_walked(rows, n, min(64, B), tile_rmw.RUN)
    assert int(copies) == np.count_nonzero(want)
    tiles = len(np.unique(rows[:n] >> 3))
    assert want.sum() >= tiles
    assert np.count_nonzero(want) <= tiles
    if B <= 64:
        assert want.sum() == tiles


@pytest.mark.parametrize("run", [1, 2, 4])
@pytest.mark.parametrize("n, capacity", [(40, 320), (48, 96), (48, 4000)],
                         ids=["dense", "every_tile", "sparse"])
def test_tile_kernel_awaits_every_copy_at_the_length_it_started(
        n, capacity, run, monkeypatch):
    """A DMA wait blocks until as many bytes as ITS descriptor names have
    arrived: one that names another length than the copy's start hangs
    the chip or runs ahead of the copy, and Pallas' interpret mode checks
    neither.  So the kernel's copies are logged as they run (a callback
    at every start and wait, by semaphore): on each semaphore starts and
    waits alternate, a wait names its start's rows, and nothing is left
    in flight when the kernel ends — over heads whose runs cross grid
    steps and, the sparse one's, take the ring twice over."""
    from swiftmpi_tpu.transfer import tile_rmw

    log, real = [], tile_rmw._pallas()

    class Logged:
        def __init__(self, src, dst, sem):
            self.copy = real[1].make_async_copy(src, dst, sem)
            # the semaphore's field and ring slot, the rows copied
            self.at = (*sem.transforms[-1].indices, dst.shape[0])
            self.sem = id(sem.ref)

        def _log(self, kind):
            jax.debug.callback(
                lambda field, slot, rows: log.append(
                    (kind, self.sem, int(rows), int(field), int(slot))),
                *map(jnp.int32, self.at))

        def start(self):
            self._log("start")
            self.copy.start()

        def wait(self):
            self._log("wait")
            self.copy.wait()

    class Pltpu:
        make_async_copy = Logged

        def __getattr__(self, name):
            return getattr(real[1], name)

    monkeypatch.setattr(tile_rmw, "_pallas", lambda: (real[0], Pltpu()))
    monkeypatch.setattr(tile_rmw, "BLOCK", 24)
    monkeypatch.setattr(tile_rmw, "RUN", run)
    monkeypatch.setattr(tile_rmw, "DEPTH", DEPTH)
    rng = np.random.default_rng(n)
    B, width = 48, 128
    rows = np.full(B, capacity, np.int32)
    rows[:n] = np.sort(rng.permutation(capacity)[:n])
    access = w2v_access(0.3, width)
    fields = {f: jnp.asarray(rng.random((capacity, width)) + 0.5,
                             jnp.float32) for f in ("h", "h2sum")}
    grads = {"h": jnp.asarray(rng.normal(size=(B, width)), jnp.float32)}
    with pltpu.force_tpu_interpret_mode():
        jax.block_until_ready(jax.jit(lambda *a: tile_rmw.rmw_tiles(
            *a, access, jnp.int32(n)))(fields, rows, grads))
    jax.effects_barrier()
    in_flight = {}
    for kind, sem, length, field, slot in log:
        if kind == "start":
            assert (sem, field, slot) not in in_flight
            in_flight[sem, field, slot] = length
        else:
            assert in_flight.pop((sem, field, slot), None) == length
    assert not in_flight
    lengths = {length // 8 for _, _, length, _, _ in log}
    assert lengths <= set(range(1, run + 1))
    if capacity < 4000:
        assert max(lengths) == run
    # two fields a copy, each read and written once; and a grid step's
    # reads run half a ring behind its last copy, for nothing
    steps = -(-n // 24)
    assert len(log) == 2 * 2 * (2 * np.count_nonzero(copies_walked(
        rows, n, 24, run)) + steps * DEPTH // 2)


def test_pallas_byte_code_is_kept_in_the_compile_cache(tmp_path):
    """The kernel's one set-up cost that is not a compile: importing
    Pallas where the installation keeps no byte code.  `tile_rmw._pallas`
    keeps it in the persistent compile cache's directory, in a process
    that has one, and leaves the interpreter's own settings as they
    were."""
    import os
    import subprocess
    import sys
    code = (
        "import sys, jax\n"
        f"jax.config.update('jax_compilation_cache_dir', {str(tmp_path)!r})\n"
        "from swiftmpi_tpu.transfer import tile_rmw\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "held = sys.dont_write_bytecode, sys.pycache_prefix\n"
        "pl, pltpu = tile_rmw._pallas()\n"
        "assert (sys.dont_write_bytecode, sys.pycache_prefix) == held\n"
        "assert pl.pallas_call and pltpu.make_async_copy\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "JAX_PLATFORMS": "cpu",
                        "PYTHONDONTWRITEBYTECODE": "1"})
    kept = list((tmp_path / "pycache").rglob("pallas_call.*.pyc"))
    assert kept, sorted(p.name for p in tmp_path.iterdir())
