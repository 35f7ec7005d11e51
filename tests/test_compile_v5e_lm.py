"""The LM cells' real-size ``trainer_step``s compiled for a v5e chip that
is described, not attached — ``tests/test_compile_v5e.py``'s kind of test
(its fixtures and helpers), in a file of their own so that the scheduler
can give the two halves to two workers: each step is a minute or two of
the chip's compiler and of Mosaic's for the attention kernel's calls.
"""

import math
import re

import jax
import jax.numpy as jnp

from tests.test_compile_v5e import (  # noqa: F401  (fixtures)
    GIB, REPO, _cell, _instructions, no_compile_cache, topo)


#: copies of a chunk's grouped products an expert layer's body holds since
#: PR 53: the loop's body (a whole chunk, the ladder's top rung) and the
#: last chunk at the one rung below it, a half (``parallel/moe.py::_walk``)
WALK_BODIES = 1 + 1


def _scope(op_name):
    """The innermost ``obs.named_scope`` of the catalog in an ``op_name``
    path, by its own name (``obs.costs.phase_of`` gives its phase)."""
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES
    for part in reversed(op_name.split("/")):
        m = re.match(r"^(?:\w+\()*([\w.\-]+)\)*$", part)
        if m and m.group(1) in DEVICE_SCOPES:
            return m.group(1)
    return None


def _kernels(text, walk_calls):
    """``op_name`` of every Pallas / Mosaic custom call of a compiled LM
    step but the attention forward walk's, after holding the walk to its
    kernel (PR 50): ``walk_calls`` — ``{scope: calls}`` — is the count of
    ``attn_fwd_tiles`` custom calls under each attention scope (the layer
    bodies the compiler made x the forward pass and the recomputation; the
    kernel runs under the scope its caller opened), and no ``while`` of a
    forward walk is left under such a scope: the loops there are the
    hand-written backward's, query tiles and their folds."""
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]*)"', text)
    walks = [n for n in names if n.endswith("/attn_fwd_tiles/pallas_call")]
    assert {s: sum(_scope(n) == s for n in walks)
            for s in set(map(_scope, walks))} == walk_calls
    loops = [n for n in re.findall(r' while\(.*?op_name="([^"]*)"', text)
             if _scope(n) in walk_calls]
    assert loops and all("transpose(jvp(" in n
                         and "rematted_computation" not in n
                         for n in loops), loops
    return [n for n in names if n not in walks]


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
    # pinned anew by PR 53: the expert walk's last chunk is a `switch` to
    # the ladder's lower rung (`parallel/moe.py::_walk`), one more copy of
    # the chunk's body, forward, recomputed and backward
    assert _instructions(text) == (10083, "09003f0ace4ec410")
    assert "ragged-dot" in text               # the compiler's grouped matmul
    # the forward walk is the kernel: the scanned attention layer's body,
    # forward and recomputed
    _kernels(text, {"attention": 2})
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
    # pinned anew by PR 53: the expert walk's last chunk is a `switch` to
    # the ladder's lower rung (`parallel/moe.py::_walk`), one more copy of
    # the chunk's body, forward, recomputed and backward
    assert _instructions(text) == (6902, "4a00fd4b7fa60a31")
    # every kernel the compiler brings is a ragged-dot one, under the names
    # the catalog books as `experts` and `^ragged-dot` matches: 3 products
    # forward and 9 backward a chunk body in the one scanned layer body
    kernels = _kernels(text, {"attention": 2})
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert set(kernels) <= set(DEVICE_SCOPES)
    assert kernels.count("ragged-dot-none") == WALK_BODIES * 12
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
    # pinned anew by PR 53: the expert walk's last chunk is a `switch` to
    # the ladder's lower rung (`parallel/moe.py::_walk`), one more copy of
    # the chunk's body, forward, recomputed and backward
    assert _instructions(text) == (22763, "eb3b026201f18308")
    # three sliding bodies and the full one, forward and recomputed
    kernels = _kernels(text, {"window_attention": 6, "attention": 2})
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert set(kernels) <= set(DEVICE_SCOPES)
    assert kernels.count("ragged-dot-none") == 3 * WALK_BODIES * 15
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
    # pinned anew by PR 53: the expert walk's last chunk is a `switch` to
    # the ladder's lower rung (`parallel/moe.py::_walk`), one more copy of
    # the chunk's body, forward, recomputed and backward
    assert _instructions(text) == (15736, "67018f26670975fa")
    kernels = _kernels(
        text, {"latent_attention": 4, "mtp_latent_attention": 2})
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert kernels.count("ragged-dot-none") == 2 * WALK_BODIES * 12
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
    # pinned anew by PR 53: the expert walk's last chunk is a `switch` to
    # the ladder's lower rung (`parallel/moe.py::_walk`), one more copy of
    # the chunk's body, forward, recomputed and backward
    assert _instructions(text) == (23257, "21b8d941c3f98a64")
    kernels = _kernels(text, {"attention": 2})    # the one `full` layer
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert kernels.count("ragged-dot-none") == 4 * WALK_BODIES * 8
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
