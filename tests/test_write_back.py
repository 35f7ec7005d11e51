"""The sparse push's row write-back (``transfer/xla.py``): every form
writes the ``local`` oracle's rows, bit for bit.

``XlaTransfer.write_back_form`` chooses, from static shapes, between
writing a push's rows one by one (``per_row``) and one sweep of the whole
field (``sweep``) and, on the fields it can (PR 34: f32 rows of whole
128-lane tiles, one TPU), writing the distinct rows at the head of the
push alone, a chunk at a time, or sweeping, as their count says at run
time (``head_rows``): a choice of device time (PERF.md section 6, PR 30,
PR 34), never of values.  Every case here runs the push in EVERY form,
twice: op by op, where the access rule's arithmetic is the oracle's own
sequence of primitives and every field must equal the numpy oracle's bit
for bit (``assert_array_equal``); and under ``jit``, as a train step runs
it, where the forms must equal each other bit for bit and stay within
one rounding of the oracle (XLA fuses the rule's arithmetic there; so it
does op by op in ``head_rows``, whose loop body is one compiled program:
held there as the jitted ones are).  The
gradients are multiples of 1/64 and every slot repeats 1, 2 or 4 times,
so the oracle's ``sum / count`` and the backend's ``sum * (1 / count)``
are the same float.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swiftmpi_tpu.cluster import SHARD_AXIS, ps_mesh
from swiftmpi_tpu.parameter import KeyIndex, SparseTable, lr_access, w2v_access
from swiftmpi_tpu.transfer.local import LocalTransfer
from swiftmpi_tpu.transfer.xla import XlaTransfer

FORMS = ("per_row", "sweep")
#: widths whose stored row is whole 128-lane tiles also take
#: ``head_rows``: bare where the shapes rule the sweep out (the loop over
#: the head's chunks alone), ``+`` / ``-`` where they do not and the count
#: at run time picks the chunks / the sweep
HEAD_FORMS = ("head_rows", "head_rows+", "head_rows-")
SHARDS = 4
CAP_PER_SHARD = 24
#: slots a chunk of `_rmw_head_rows` here: the 12 distinct rows of a
#: batch are two chunks, the second one ragged
HEAD_CHUNK = 8


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
    from swiftmpi_tpu.transfer import xla

    if sharded and len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} virtual devices")
    mesh = ps_mesh(n=SHARDS) if sharded else None
    monkeypatch.setattr(xla, "_HEAD_CHUNK", HEAD_CHUNK)
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
    forms = FORMS + (HEAD_FORMS if stored % 128 == 0 else ())
    slots, grad = _batch(case, table, stored, seed=width + 7 * mean)
    state_np = {f: np.asarray(v) for f, v in table.state.items()}
    want = LocalTransfer().push(state_np, slots, {family: grad}, access,
                                mean=mean)

    def push(backend, state, slots, grad):
        return backend.push(state, slots, {family: grad}, access, mean=mean)

    eager, jitted = {}, {}
    for form_case in forms:
        form = form_case.rstrip("+-")
        backend = XlaTransfer(dense_apply=False,
                              shards=SHARDS if sharded else 1)
        monkeypatch.setattr(backend, "write_back_form",
                            lambda n, fields, form=form: form)
        if form == "head_rows":
            # what the shapes say of the sweep, and what a row written
            # weighs against it: here, the case's
            monkeypatch.setattr(
                backend, "_static_form", lambda n, fields, may=form_case
                != form: "sweep" if may else "per_row")
            monkeypatch.setattr(xla, "_ROW_WRITE_AS_SWEPT_BYTES",
                                1 << 24 if form_case.endswith("-") else 1)
        out = push(backend, table.state, jnp.asarray(slots),
                   jnp.asarray(grad))
        eager[form_case] = {f: np.asarray(v) for f, v in out.items()}
        if len(slots):
            touched = access.touched_fields([family])
            assert backend.resolved_write_back == dict.fromkeys(touched, form)
        out = jax.jit(push, static_argnums=0)(
            backend, table.state, jnp.asarray(slots), jnp.asarray(grad))
        if sharded:
            assert out[family].sharding.is_equivalent_to(
                table.state[family].sharding, 2)
        jitted[form_case] = {f: np.asarray(v) for f, v in out.items()}
        for f in access.fields:
            if form == "head_rows":
                np.testing.assert_array_equal(jitted[form_case][f],
                                              eager[form_case][f],
                                              err_msg=f"{form_case}:{f}")
            else:
                np.testing.assert_array_equal(want[f], eager[form_case][f],
                                              err_msg=f"{form_case}:{f}")
            np.testing.assert_allclose(want[f], jitted[form_case][f],
                                       rtol=1e-6, atol=1e-7,
                                       err_msg=f"jit {form_case}:{f}")
    for form_case in forms:
        for f in access.fields:
            np.testing.assert_array_equal(jitted["sweep"][f],
                                          jitted[form_case][f],
                                          err_msg=f"{form_case}:{f}")
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
    # one TPU, rows of whole 128-lane tiles: the head's rows, any size
    *((*c[:4], "tpu", "head_rows") for c in CELLS[:6]),
    (100_000, 3_900_004, 128, 1, "tpu", "head_rows"),
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
