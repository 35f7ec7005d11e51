"""Micro A/B on the chip: forms of the sparse push's row write-back.

``transfer/xla.py::_push_sparse`` gathers the current rows of a field at
the batch's representative slots, applies the access method and writes the
rows back.  This script times that read-modify-write alone, on one donated
``f32[2340001, 300]`` field (``cbow2m-demo``'s table), for each candidate
form of the write-back and the issue's two batch sizes (plus half-padded
ones for the two forms the program chooses between), and reads the times
from a device trace (ISSUE 30; the table is in PERF.md section 6).  The
field is stored as the table stores it, in the compiler's column-major
default; ``--layout row_major`` pins it row-major in and out of every
program instead (PR 32's reading: what the write-back costs once no step
copies a whole field, ROADMAP S1 / D0 / D10).  ``--width 384 --cases
head`` (PR 34) times the field as it is stored since PR 32, 384 lanes and
row-major by default, at the cells' own pushes with their distinct valid
rows at the head: the sweep and the per-row write beside the program's own
tile kernel (``transfer/tile_rmw.py``, PR 47: ``tiles`` on the one field,
``tiles_x2`` on a parameter and its AdaGrad accumulator as a word2vec push
has them; the ring depths and block sizes tried cost the same, PERF.md
section 6, and left the script with the kernel's arguments), the read half for
the floor, and two heads as long as a sparse push gets (a quarter and
nearly half of the rows: beyond that a push is dense) for the sweep
against the kernel where most tiles are named.  ``--width 384 --cases
runs`` (PR 48) prices a COPY of the kernel by its length: heads of 98,304
tiles, a row each, in runs of exactly 1, 2, 4 and 8 adjacent tiles, every
run one copy each way (``tiles_x2@8``: `tile_rmw.RUN` = 8) — the same rows
and bytes, an eighth of the copies; then the cells' own heads, drawn as
the cells draw them (`_cell_head`: a row's slot is its frequency rank, so
the head of the table is dense), at 1, 2, 4 and 8 tiles a copy at most,
and the kernel without its copies, without its update and without both.

    python scripts/writeback_micro.py                # on the chip
    python scripts/writeback_micro.py --tree .parent --out parent.json ...
        # the same cases on another checkout's kernel, in the same call
    JAX_PLATFORMS=cpu python scripts/writeback_micro.py --compile-only DIR
        # no chip: compile every form for a described v5e, write the HLO

Writes ``chiprun_out/writeback_micro.json`` and prints the table.
"""

import argparse
import contextlib
import glob
import json
import os
import re
import shutil
import sys

#: the checkout whose ``swiftmpi_tpu`` is timed: this one, or ``--tree DIR``
#: (a parent's ``git archive``: its kernel in the same call, on the same
#: heads)
TREE = os.path.abspath(
    sys.argv[sys.argv.index("--tree") + 1] if "--tree" in sys.argv
    else os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, TREE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from swiftmpi_tpu.parameter import w2v_access  # noqa: E402
from swiftmpi_tpu.transfer import tile_rmw  # noqa: E402

CAP, D = 2340001, 300
#: (valid rows, batch length): the tail is ``capacity`` padding, as the
#: push's ``rep_slots`` has after its dedup
SIZES = {"5k": (5000, 5500), "100k": (100000, 110000),
         "2.7k_of_5.5k": (2750, 5500), "50k_of_110k": (50000, 110000),
         "80k_of_180k": (80000, 180224),
         # the cells' other pushes at their 56.5 % fill (PR 32)
         "12k_of_20k": (11600, 20480), "70k_of_123k": (69500, 122880),
         "93k_of_164k": (92600, 163840),
         # the cells' pushes by their DISTINCT valid rows (ISSUE 34):
         # cbow2m-demo; sg2m-b2k inputs, targets; cbow2m-b16k contexts,
         # targets; the worst push that shape allows
         "3.5k_of_5.5k": (3500, 5500), "2k_of_20k": (2000, 20480),
         "60k_of_123k": (60000, 122880), "63k_of_164k": (63000, 163840),
         "146k_of_180k": (146000, 180224), "180k_of_180k": (180224, 180224),
         # a quarter of the rows, and the longest sparse push
         # (`XlaTransfer.pushes_dense`: half the rows go dense)
         "585k_of_585k": (585000, 585000), "1.1m_of_1.1m": (1100000, 1100000)}
#: tiles of a `RUN_SIZES` head, a row each
RUN_TILES = 98304
#: heads whose named tiles stand in runs of exactly so many, an un-named
#: tile between two runs: ``runs_of_<L>``
RUN_SIZES = {f"runs_of_{L}": L for L in (1, 2, 4, 8)}
#: the cells' pushes as the cells draw them: (slots, of them centers or
#: positive contexts, the rest negatives, uniform keys) — cbow2m-b16k's
#: targets, sg2m-b2k's, cbow2m-demo's, cbow2m-b16k-uniform's
CELL_SIZES = {"zipf_b16k": (180224, 16384, False),
              "zipf_sg": (122880, 20480, False),
              "zipf_demo": (5500, 500, False),
              "flat_b16k": (180224, 16384, True)}
SIZES.update({size: (RUN_TILES, RUN_TILES) for size in RUN_SIZES})
# a cell's head is as long as its draw makes it: `measure` writes it here
SIZES.update({size: (slots, slots) for size, (slots, *_) in
              CELL_SIZES.items()})
ISSUE_SIZES = ("5k", "100k")
HEAD_SIZES = ("3.5k_of_5.5k", "2k_of_20k", "60k_of_123k", "63k_of_164k",
              "146k_of_180k", "180k_of_180k")
DENSE_SIZES = ("585k_of_585k", "1.1m_of_1.1m")
HEAD_FORMS = ("a", "b", "gather_only", "tiles", "tiles_x2")
DENSE_FORMS = ("a", "tiles", "tiles_x2")
RUNS = 4
#: ``layout.Format`` of the field in and out of every program, or ``None``
#: for the compiler's default (set from ``--layout``)
FIELD_FORMAT = None


def _rmw(x, rep):
    valid = rep < CAP
    cur = jnp.take(x, jnp.where(valid, rep, 0), axis=0)
    return cur, valid


def _apply(cur, g):
    # stand-in for access.apply_push: elementwise on the gathered rows
    return cur - 0.05 * g * lax.rsqrt(cur * cur + 1.0)


def _set(x, rep, upd, mode="drop", **hints):
    return x.at[rep].set(upd, mode=mode, **hints)


def _route_pad(rep, valid, upd, cur, to_last):
    """Padding rewrites a real row with that row's own new value (the
    last valid representative keeps the indices ascending; the first
    does not), so no index is out of bounds."""
    n = jnp.sum(valid)
    at = jnp.maximum(n - 1, 0) if to_last else 0
    tgt_pad = jnp.where(n > 0, rep[at], 0)
    val_pad = jnp.where(n > 0, upd[at], cur[at])
    return (jnp.where(valid, rep, tgt_pad),
            jnp.where(valid[:, None], upd, val_pad[None, :]))


def form_a(x, rep, g):          # today's call
    cur, _ = _rmw(x, rep)
    return _set(x, rep, _apply(cur, g), indices_are_sorted=True,
                unique_indices=True)


def form_b(x, rep, g):          # no hints
    cur, _ = _rmw(x, rep)
    return _set(x, rep, _apply(cur, g))


def form_c(x, rep, g):          # unique only
    cur, _ = _rmw(x, rep)
    return _set(x, rep, _apply(cur, g), unique_indices=True)


def form_d(x, rep, g):          # sorted only
    cur, _ = _rmw(x, rep)
    return _set(x, rep, _apply(cur, g), indices_are_sorted=True)


def form_e(x, rep, g):          # today's, gather kept out of the fusion
    cur, _ = _rmw(x, rep)
    upd = lax.optimization_barrier(_apply(cur, g))
    return _set(x, rep, upd, indices_are_sorted=True, unique_indices=True)


def form_f(x, rep, g):          # padding -> last valid row, in bounds
    cur, valid = _rmw(x, rep)
    tgt, vals = _route_pad(rep, valid, _apply(cur, g), cur, True)
    return _set(x, tgt, vals, mode="promise_in_bounds")


def form_f_s(x, rep, g):        # ... + sorted (still true)
    cur, valid = _rmw(x, rep)
    tgt, vals = _route_pad(rep, valid, _apply(cur, g), cur, True)
    return _set(x, tgt, vals, mode="promise_in_bounds",
                indices_are_sorted=True)


def form_g(x, rep, g):          # a loop of row dynamic_update_slices
    cur, valid = _rmw(x, rep)
    tgt, vals = _route_pad(rep, valid, _apply(cur, g), cur, False)

    def body(i, acc):
        return lax.dynamic_update_slice(
            acc, lax.dynamic_slice_in_dim(vals, i, 1), (tgt[i], 0))
    return lax.fori_loop(0, rep.shape[0], body, x)


class _Access:
    """`_apply` as an access method of the one field ``x``."""

    @staticmethod
    def apply_push(current, grads):
        return {"x": _apply(current["x"], grads["x"])}


def _fields(out):
    """The fields `tile_rmw.rmw_tiles` returns (since PR 48 beside its
    count of copies)."""
    return out[0] if isinstance(out, tuple) else out


def form_tiles(x, rep, g):  # the program's tile kernel
    return _fields(tile_rmw.rmw_tiles(
        {"x": x}, rep, {"x": g}, _Access,
        jnp.sum(rep < CAP, dtype=jnp.int32)))["x"]


def form_tiles_x2(xs, rep, g, access=None):  # ... AdaGrad, 2 fields
    out = _fields(tile_rmw.rmw_tiles(
        dict(zip(("h", "h2sum"), xs)), rep, {"h": g},
        access or w2v_access(0.05, D), jnp.sum(rep < CAP, dtype=jnp.int32)))
    return out["h"], out["h2sum"]


class _NoCopy:
    """A DMA that is neither started nor awaited (the ablation)."""

    def __init__(self, *_):
        pass

    start = wait = __init__


class _SameRows:
    """The access rule that leaves its rows as they are (the ablation:
    the kernel's sublane loads and stores stay, the arithmetic goes)."""

    @staticmethod
    def apply_push(current, grads):
        return dict(current)


@contextlib.contextmanager
def _kernel(run, depth, copies):
    """`tile_rmw` with ``run`` tiles a copy at most and ``depth`` ring
    slots (0: its own) and, for the ablation, without its copies, while a
    form is traced."""
    held = getattr(tile_rmw, "RUN", 1), tile_rmw.DEPTH, tile_rmw._pallas
    pl, pltpu = held[2]()

    class NoDma:
        make_async_copy = _NoCopy

        def __getattr__(self, name):
            return getattr(pltpu, name)
    tile_rmw.RUN = run or held[0]
    tile_rmw.DEPTH = depth or held[1]
    if not copies:
        tile_rmw._pallas = lambda: (pl, NoDma())
    try:
        yield
    finally:
        tile_rmw.RUN, tile_rmw.DEPTH, tile_rmw._pallas = held


def _run_of(form):
    """``(C, ring slots)`` of ``tiles...[@C[xSLOTS]][-...]``; 0: the
    kernel's own."""
    run, _, depth = form.partition("-")[0].partition("@")[2].partition("x")
    return int(run or 0), int(depth or 0)


def _tiles_x2_as(form):
    """``tiles_x2[@C[xSLOTS]][-copies|-update|-both]``: `form_tiles_x2` at
    C tiles a copy at most (and so many ring slots), less what the
    ablation takes out."""
    less = form.partition("-")[2]
    access = _SameRows if less in ("update", "both") else None

    def form_x2(xs, rep, g):
        with _kernel(*_run_of(form), copies=less not in ("copies", "both")):
            return form_tiles_x2(xs, rep, g, access)
    return form_x2


def _copies(form, head):
    """Copies ``form``'s kernel makes one way a field for the ascending
    rows ``head``, and the tiles they move: a run of adjacent tiles is
    cut every `tile_rmw.RUN` tiles and where a grid step ends."""
    if not form.startswith("tiles"):
        return None, None
    run = _run_of(form)[0] or getattr(tile_rmw, "RUN", 1)
    step, tile = np.arange(len(head)) // tile_rmw.BLOCK, head >> 3
    new = np.r_[True, (tile[1:] != tile[:-1]) | (step[1:] != step[:-1])]
    step, tile = step[new], tile[new]
    first = np.flatnonzero(np.r_[True, (tile[1:] != tile[:-1] + 1)
                                 | (step[1:] != step[:-1])])
    return (int(np.sum(-(-np.diff(np.r_[first, len(tile)]) // run))),
            len(tile))


def form_gather_only(x, rep, g):  # the floor: the read half, no write
    cur, _ = _rmw(x, rep)
    return _apply(cur, g)


FORMS = {"a": form_a, "b": form_b, "c": form_c, "d": form_d, "e": form_e,
         "f": form_f, "f_s": form_f_s, "g": form_g,
         "gather_only": form_gather_only, "tiles": form_tiles,
         "tiles_x2": form_tiles_x2}


def _jitted(form, size):
    fn = FORMS.get(form) or _tiles_x2_as(form)

    def wb(x, rep, g):
        return fn(x, rep, g)
    wb.__name__ = f"wb_{form}_{size}"
    if form == "gather_only":
        return jax.jit(wb)
    return jax.jit(wb, donate_argnums=0, out_shardings=FIELD_FORMAT)


def _set_layout(layout, sharding):
    global FIELD_FORMAT
    if layout == "row_major":
        from jax.experimental.layout import Format, Layout
        FIELD_FORMAT = Format(Layout(major_to_minor=(0, 1)), sharding)
        # an executable read back from the persistent cache hands out
        # arrays that claim the default layout (jaxlib 0.9.0;
        # scripts/layout_cache_probe.py): compile everything here
        jax.config.update("jax_enable_compilation_cache", False)


def _cases(which):
    if which == "runs":
        # a copy by its length; a length-8 head tile by tile; the floor
        yield from (("tiles_x2@8", size) for size in RUN_SIZES)
        yield from (("tiles_x2@1", "runs_of_8"), ("tiles_x2@8-copies",
                                                  "runs_of_8"))
        # the cells' heads by the tiles a copy may take, and against XLA
        yield from ((f"tiles_x2@{run}", size) for size in CELL_SIZES
                    for run in (1, 2, 4, 8))
        yield from ((form, "zipf_b16k") for form in (
            "a", "tiles", "tiles_x2-copies", "tiles_x2-update",
            "tiles_x2-both", "tiles_x2@8x16", "tiles_x2@8x64", "tiles_x2@16"))
        # PR 47's uniform heads: the fewest neighbours a draw leaves
        yield from (("tiles_x2", size) for size in (
            "3.5k_of_5.5k", "60k_of_123k", "146k_of_180k"))
        return
    if which == "head":
        yield from ((form, size) for size in HEAD_SIZES
                    for form in HEAD_FORMS)
        yield from ((form, size) for size in DENSE_SIZES
                    for form in DENSE_FORMS)
        return
    for size in SIZES:
        for form in FORMS:
            if (size in HEAD_SIZES + DENSE_SIZES + tuple(RUN_SIZES)
                    + tuple(CELL_SIZES) or form.startswith("tiles")):
                continue
            if form == "g" and size != "5k":
                continue
            if size not in ISSUE_SIZES and form not in ("a", "b"):
                continue
            yield form, size


def compile_only(out_dir, layout, which, only):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    _set_layout(layout, dev)
    os.makedirs(out_dir, exist_ok=True)
    for form, size in _cases(which):
        if only is not None and form not in only and size not in only:
            continue
        B = SIZES[size][1]
        field = jax.ShapeDtypeStruct((CAP, D), jnp.float32,
                                     sharding=FIELD_FORMAT or dev)
        c = _jitted(form, size).lower(
            (field, field) if form.startswith("tiles_x2") else field,
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=dev),
            jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=dev)).compile()
        mem = c.memory_analysis()
        text = c.as_text()
        with open(os.path.join(out_dir, f"wb_{form}_{size}.hlo"), "w") as f:
            f.write(text)
        print(f"{form:12s}{size:14s}temp {mem.temp_size_in_bytes / 2**30:5.2f}"
              f" GiB  alias {mem.alias_size_in_bytes / 2**30:5.2f} GiB  "
              f"whole-field copies "
              f"{len(re.findall(rf'= f32.{CAP},{D}.[^ ]* copy', text))}",
              flush=True)


def _cell_head(slots, positives, uniform, rng, vocab=1_800_000,
               stream=8_000_000):
    """The distinct rows of a target push as a word2vec cell of the
    benchmark draws it (``benchmark/families/w2v.py``): every key once and
    the rest of the stream Zipf (exponent 1; ``uniform``: flat), a row's
    slot its rank by count, centers from the stream subsampled at 1e-4,
    negatives from ``counts ** 0.75``."""
    p = np.ones(vocab) if uniform else 1.0 / np.arange(1, vocab + 1)
    counts = -np.sort(-(1 + np.bincount(rng.choice(
        vocab, stream - vocab, p=p / p.sum()), minlength=vocab)))
    f = counts / counts.sum()
    kept = f * np.minimum(1.0, np.sqrt(1e-4 / f) + 1e-4 / f)
    drawn = counts ** 0.75
    return np.unique(np.concatenate([
        rng.choice(vocab, positives, p=kept / kept.sum()),
        rng.choice(vocab, slots - positives, p=drawn / drawn.sum())]))


def _head(size, n, rng):
    """The ``n`` ascending distinct rows of the head ``size``."""
    if size in RUN_SIZES:
        L = RUN_SIZES[size]
        tile = np.arange(n)
        return 8 * (tile + tile // L) + rng.integers(0, 8, n)
    if size in CELL_SIZES:
        return _cell_head(*CELL_SIZES[size], rng)[:n]
    # whole tiles only: the rows of the last, partial one are not the
    # kernel's (`transfer/xla.py::_rmw_tiles`)
    return np.sort(rng.choice(CAP - CAP % tile_rmw.TILE, n, replace=False))


def _reduce(trace_dir):
    """``(ms a run, {op: ms a run})`` of the one program the capture
    holds ``RUNS`` executions of, from the first device plane."""
    from swiftmpi_tpu.obs.profiler import _line_events, self_times
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name.startswith("/device:TPU:0"))
    lines = {ln.name: _line_events(ln) for ln in plane.lines}
    runs = lines["XLA Modules"]
    assert len(runs) == RUNS, [r[2] for r in runs]
    ops = {}
    for text, ns, _ in self_times(lines["XLA Ops"]):
        op = text.split(" = ")[0].lstrip("%")
        ops[op] = ops.get(op, 0.0) + ns / 1e6 / RUNS
    ops = {k: round(v, 4) for k, v in sorted(
        ops.items(), key=lambda kv: -kv[1]) if v > 0.02}
    return sum(e - s for s, e, _ in runs) / 1e6 / RUNS, ops


def measure(only, layout, which, out_name):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs the chip, found {dev.platform}")
    _set_layout(layout, jax.sharding.SingleDeviceSharding(dev))
    init = jax.jit(lambda k: jax.random.normal(k, (CAP, D), jnp.float32),
                   out_shardings=FIELD_FORMAT)

    @jax.jit
    def digest(x, rep):
        rows = jnp.take(x, jnp.where(rep < CAP, rep, 0), axis=0)
        return jnp.sum(rows), jnp.sum(x)

    rng = np.random.default_rng(0)
    inputs = {}
    for size in dict.fromkeys(size for _, size in _cases(which)):
        n, B = SIZES[size]
        head = _head(size, n, rng)
        rep = np.full((B,), CAP, np.int32)
        rep[:len(head)] = head
        SIZES[size] = (len(head), B)
        inputs[size] = (jnp.asarray(rep), jax.random.normal(
            jax.random.key(1), (B, D), jnp.float32))
    result = {"device": dev.device_kind, "layout": layout, "tree": TREE,
              "capacity": CAP, "width": D,
              "sizes": SIZES, "runs": RUNS,
              "tile_block": tile_rmw.BLOCK, "tile_depth": tile_rmw.DEPTH,
              "tile_run": getattr(tile_rmw, "RUN", 1),
              "cases": {}}
    trace_dir = os.path.join("chiprun_out", "writeback_trace")
    digests = {}
    for form, size in _cases(which):
        if only is not None and form not in only and size not in only:
            continue
        fn = _jitted(form, size)
        rep, g = inputs[size]
        x = init(jax.random.key(0))
        if form.startswith("tiles_x2"):            # the accumulator: positive
            x = (x, jnp.square(init(jax.random.key(2))))
        out = fn(x, rep, g)                 # compiles; the digest's run
        keep = form != "gather_only"
        if keep:
            digests[form, size] = [float(v) for v in digest(
                out[0] if form.startswith("tiles_x2") else out, rep)]
            x = out
        jax.block_until_ready((x, out))
        # a capture of its own: programs that compile to one executable
        # share a name in a common capture
        jax.profiler.start_trace(trace_dir)
        for _ in range(RUNS):
            out = fn(x, rep, g)
            if keep:
                x = out
        jax.block_until_ready((x, out))
        jax.profiler.stop_trace()
        ms, ops = _reduce(trace_dir)
        shutil.rmtree(trace_dir)            # too big to bring back
        del x, out
        # the row sum and the field sum against form ``a``'s: equal, or
        # apart by the rounding of another compiler's `rsqrt`
        same = (digests[form, size] == digests.get(("a", size))
                if keep and not form.startswith("tiles_x2") else None)
        if same is False and ("a", size) in digests:
            same = max(abs(v - w) / abs(w) for v, w in zip(
                digests[form, size], digests["a", size]))
        copies, tiles = _copies(form, np.asarray(rep)[:SIZES[size][0]])
        result["cases"][f"{form}.{size}"] = {
            "ms_per_run": ms, "rows": SIZES[size][0], "tiles": tiles,
            "copies": copies, "ops": ops, "same_as_a": same}
        top = ", ".join(f"{k} {v:.3f}" for k, v in list(ops.items())[:6])
        print(f"{form:18s}{size:14s}{ms:9.3f} ms a run  rows "
              f"{SIZES[size][0]} tiles {tiles} copies {copies}  "
              f"same_as_a={same}  [{top}]", flush=True)
    with open(os.path.join("chiprun_out", out_name), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compile-only", metavar="DIR", default=None)
    ap.add_argument("--only", nargs="*", default=None,
                    help="forms or sizes to run (default: all)")
    ap.add_argument("--layout", choices=("row_major", "default"),
                    default="default",
                    help="how the field is stored (default: as the table "
                         "stores it, the compiler's choice)")
    ap.add_argument("--tree", default=TREE,
                    help="a checkout to take swiftmpi_tpu from")
    ap.add_argument("--out", default="writeback_micro.json",
                    help="the result's name under chiprun_out/")
    ap.add_argument("--width", type=int, default=D,
                    help="lanes of a stored row (384: the table's since "
                         "PR 32, row-major by default)")
    ap.add_argument("--cases", choices=("forms", "head", "runs"),
                    default="forms",
                    help="forms: PR 30's candidates at its sizes; head: "
                         "the cells' pushes, distinct rows at the head; "
                         "runs: the tile kernel's copies by their length")
    args = ap.parse_args()
    D = args.width
    if args.compile_only:
        compile_only(args.compile_only, args.layout, args.cases, args.only)
    else:
        os.makedirs("chiprun_out", exist_ok=True)
        measure(args.only, args.layout, args.cases, args.out)
