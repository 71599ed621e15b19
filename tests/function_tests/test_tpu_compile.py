"""The main path's kernels and the 300M train step, compiled for a
described (not attached) TPU v5e at the sizes ``chip_smoke.py`` runs.

Nothing executes here: the TPU compiler is asked whether the program is
legal and how much device memory it needs.  Every other test in the
suite runs the Pallas kernels through the interpreter, which accepts
tilings, VMEM budgets and SMEM operands the chip's compiler refuses.

The topology is described inside a module-scoped fixture (never at
import): only the xdist worker that runs this file loads the TPU
library.  This is the ONLY test file that does.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from chainermn_tpu.ops.pallas_attention import flash_attention

HBM_BYTES = 16 * 2 ** 30        # one v5e chip
B, T, H, D = 8, 2048, 16, 64    # chip_smoke's attention shape


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable cannot be read back from the
    # persistent cache without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(sharding, shape=(B, T, H, D)):
    """q, k and v of one shape; a 192-wide one is latent attention's,
    whose values are 128 wide."""
    s = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    v = s if shape[-1] != 192 else jax.ShapeDtypeStruct(
        shape[:-1] + (128,), jnp.bfloat16, sharding=sharding)
    return s, s, v


# the typed cells' windowed kernels: 2 x 8,192 tokens, 128-wide heads
# (32 of them here), windows of 512 and 1,024 in 1,024-wide blocks, so
# the grid is the band's two blocks and the index maps read the
# prefetched offsets; then the cells' own shapes, for the backward's
# accumulator over the query length: OPT's, Kimi's latent layer (keys
# 192 wide, values 128) and Qwen3-Next's 256-wide heads, the longest
# and widest the kernel meets (the compiler needs 46.6 and 44.0 MiB of
# VMEM for them, by bisection of the limit, PR 44)
TYPED = (2, 8192, 32, 128)
FLASH_SHAPES = pytest.mark.parametrize("shape,window", [
    ((B, T, H, D), None), ((B, T, H, D), 1024), (TYPED, 512),
    (TYPED, 1024), ((4, 2048, 32, 64), None), ((1, 16384, 32, 192), None),
    ((1, 16384, 16, 256), None)],
    ids=["full", "window1024", "8192-window512", "8192-window1024",
         "opt", "kimi-mla", "qwen3-next"])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel is not in the compiled program"
    return compiled


def _flash_kernels(text, scope=""):
    """The flash kernels' call sites in a compiled program's text (a
    scanned layer's count once), optionally those under one scope."""
    return sum('custom_call_target="tpu_custom_call"' in line
               and "pallas_call" in line and scope in line
               for line in text.splitlines())


@functools.cache
def _flash_grad_text(one_chip, shape, window):
    """The compiled gradient of the kernel's sum at one of
    ``FLASH_SHAPES``, once for the two tests that read it."""
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window).astype(
            jnp.float32).sum()

    return _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    *_qkv(one_chip, shape)).as_text()


@FLASH_SHAPES
def test_flash_forward_compiles(one_chip, shape, window):
    """Read off the gradient's program, where that compiles: the VJP's
    forward rule and the primal make the same call of the same kernel
    (``pallas_attention._fwd``), so the compiler has taken it there at
    this shape.  Where the gradient's does not compile, that is the
    backward test's to say, and the forward is asked about alone."""
    try:
        text = _flash_grad_text(one_chip, shape, window)
    except Exception:
        text = _compile(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window),
            *_qkv(one_chip, shape)).as_text()
        assert _flash_kernels(text) == 1
    else:
        assert _flash_kernels(text, ")/jvp(attn.core)/pallas_call") == 1


@FLASH_SHAPES
def test_flash_backward_compiles(one_chip, shape, window):
    text = _flash_grad_text(one_chip, shape, window)
    # the forward and the one backward kernel
    assert _flash_kernels(text) == 2
    assert _flash_kernels(
        text, ")/transpose(jvp(attn.core))/pallas_call") == 1


@pytest.mark.parametrize("t,window", [(512, None), (4096, 512)],
                         ids=["full", "4096-window512"])
def test_flash_ring_call_form_compiles(one_chip, t, window):
    """The per-pair call ring attention makes: lse returned and
    differentiated, global offsets as TRACED scalars (the scalar-prefetch
    operand the index maps read; with a window the grid is the three
    blocks a band can touch wherever the offsets put it)."""
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def loss(q, k, v, q_off, k_off):
        o, lse = flash_attention(
            q, k, v, causal=True, window=window, q_offset=q_off,
            k_offset=k_off, return_lse=True)
        return o.astype(jnp.float32).sum() + lse.sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             *_qkv(one_chip, (B, t, H, D)), off, off)


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_pair_compiles_on_four_chips(topo, layout):
    """The whole ring (kernel pair + ppermute scan) under shard_map over
    the four described devices, forward and backward, GQA 16q/4kv."""
    from chainermn_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("seq",))
    spec = P(None, "seq")
    sh = NamedSharding(mesh, spec)
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((B, T, 4, D), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        o = jax.shard_map(
            lambda q, k, v: ring_attention(
                q, k, v, axis_name="seq", causal=True, use_flash=True,
                layout=layout),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)(q, k, v)
        return o.astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv).as_text()
    assert "collective-permute" in text


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_kda_pair_kernels_compile(topo, one_chip, backward):
    """The two kernels that make a chunk's pair weights in VMEM
    (``ops/kda.py``), at the Kimi cell's slab step: 32 heads x 4 chunks
    of 64 tokens, keys 128 wide, float32."""
    from chainermn_tpu.ops import kda
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh

    x = jax.ShapeDtypeStruct((1, 32, 4, 64, 128), jnp.float32,
                             sharding=one_chip)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    weights = tracing_for_mesh(mesh, kda._pair_weights)

    def loss(q, k, G):
        return sum(jnp.sum(jnp.sin(a)) for a in weights(q, k, G))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else weights
    text = _compile(fn, x, x, x).as_text()
    assert sum('custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines()) == 1 + backward


# the three cells' convolutions (``ops/recurrent.py``): the tensor's
# shape (Kimi's q, k, v of 32 heads side by side, as the projection
# leaves them), the parts it leaves in (the delta rules' by head),
# whether it has a bias
CONV_CELLS = {
    "kimi": ((1, 16384, 12288), ((32, 128),) * 3, False),
    "qwen3-next": ((1, 16384, 8192), ((16, 128), (16, 128), (32, 128)),
                   False),
    "nemotron": ((2, 8192, 6144), (4096, 1024, 1024), True),
}


def _conv_sites(text, scope):
    """The convolution kernels' call sites under ``scope`` in a
    compiled program's text, after checking that nothing under it pads
    a tensor (the plain form's copy of the whole projection, and
    autodiff's of its cotangent)."""
    under = [line for line in text.splitlines() if scope in line]
    assert not any(" pad(" in line for line in under), \
        [line[:200] for line in under if " pad(" in line]
    return [line for line in under
            if "pallas_call" in line and "tpu_custom_call" in line]


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("cell", list(CONV_CELLS))
def test_conv_kernels_compile(topo, one_chip, cell, backward):
    """``causal_conv_silu``'s two kernels alone at the three cells' real
    shapes, float32: Mosaic lowers the rotations along the sublanes and
    the blocks fit VMEM; one kernel forward, a second backward, no pad,
    and, where the parts leave as the kernel wrote them (by head they
    are swapped into this test's row-major results), no tensor beside
    the arguments and the results forward."""
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh
    from chainermn_tpu.ops.recurrent import causal_conv_silu

    shape, split, biased = CONV_CELLS[cell]
    like = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    conv = tracing_for_mesh(mesh, lambda y, w, b: causal_conv_silu(
        y, w, b, split))

    def loss(y, w, b):
        return sum(jnp.sum(jnp.sin(part)) for part in conv(y, w, b))

    fn = jax.grad(loss, argnums=(0, 1, 2) if biased else (0, 1)) \
        if backward else conv
    compiled = _compile(fn, like(shape), like(shape[2:] + (4,)),
                        like(shape[2:]) if biased else None)
    assert len(_conv_sites(compiled.as_text(), "")) == 1 + backward
    if not backward and not any(isinstance(p, tuple) for p in split):
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_gated_conv_kernels_compile(topo, one_chip, backward):
    """``gated_short_conv``'s two kernels alone at the LFM2 cell's real
    shape (4 x 8,192 tokens, [B | C | x] of 2,048 channels each, 3
    taps), float32: Mosaic lowers them and the blocks fit VMEM (seven
    of a lane tile going back); one kernel forward, a second backward,
    and forward no pad and no tensor beside the argument and the
    result.  (Backward the three parts' cotangents are laid side by
    side again: alone XLA pads and adds them; in the cell's step the
    same pass is the cast to bfloat16 the projection's backward needs
    anyway, three in-place slices of one bfloat16 buffer.)"""
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh
    from chainermn_tpu.ops.recurrent import gated_short_conv

    like = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    conv = tracing_for_mesh(mesh, gated_short_conv)
    fn = jax.grad(lambda a, w: jnp.sum(jnp.sin(conv(a, w))), (0, 1)) \
        if backward else conv
    compiled = _compile(fn, like((4, 8192, 6144)), like((2048, 3)))
    text = compiled.as_text()
    assert sum("pallas_call" in line and "tpu_custom_call" in line
               for line in text.splitlines()) == 1 + backward
    if not backward:
        assert not _conv_sites(text, "")[1:]       # and nothing padded
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# the five typed cells' expert layers at their first rung, bfloat16:
# rows of the sorted buffer, experts held, model width, experts' width
GROUPED_CELLS = {
    "mellum": (65536, 16, 2304, 896),
    "laguna": (32768, 32, 2048, 512),
    "kimi": (8192, 8, 2304, 1024),
    "nemotron": (12288, 8, 2688, 1920),
    "qwen3-next": (20480, 32, 2048, 512),
}


def _grouped_sites(text, scope="moe/experts"):
    """The grouped-matmul kernels' call sites under ``scope`` in a
    compiled program's text."""
    return [line for line in text.splitlines()
            if scope in line and "pallas_call" in line
            and "tpu_custom_call" in line]


@pytest.mark.parametrize("cell", list(GROUPED_CELLS))
def test_grouped_matmul_kernels_compile(topo, one_chip, cell):
    """``grouped_dense`` traced for the chip, through one gated expert
    layer at a cell's real shapes and the tile rule's own tiles: three
    kernels forward and six backward (a product's rows' backward is the
    forward kernel on the transposed weights, its weights' backward the
    third), every one under the scope the benchmark's readers find it
    by, and Mosaic takes the tiles and the VMEM they ask for."""
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh
    from chainermn_tpu.parallel.expert import grouped_dense
    from chainermn_tpu.utils.telemetry import device_scope

    R, G, D, F = GROUPED_CELLS[cell]
    like = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((G,), jnp.int32, sharding=one_chip)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))

    def loss(rows, w1, w3, w2, sizes):
        with device_scope("moe/experts"):
            y = jax.nn.silu(grouped_dense(rows, w1, sizes)) \
                * grouped_dense(rows, w3, sizes)
            return jnp.sum(jnp.sin(grouped_dense(y, w2, sizes)))

    fn = tracing_for_mesh(mesh, jax.grad(loss, argnums=(0, 1, 2, 3)))
    text = _compile(fn, like(R, D), like(G, D, F), like(G, D, F),
                    like(G, F, D), sizes).as_text()
    assert len(_grouped_sites(text)) == 9
    assert "ragged-dot" not in text


def _compile_step(mc, cfg, opt, batch, seq):
    """``make_train_step`` compiled for the described devices of ``mc``
    (shapes only: there is no device to hold an array)."""
    from chainermn_tpu.models import (
        init_transformer, make_train_step, param_specs,
    )

    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=mc.sharding(*s)),
        shapes, param_specs(cfg))
    opt_shapes = jax.eval_shape(opt.init, params)
    # Adam's moments follow their parameter's sharding (what
    # shard_opt_state pins); scalars are replicated
    by_shape = {}
    for leaf in jax.tree.leaves(params):
        by_shape.setdefault(leaf.shape, leaf.sharding)
    opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=by_shape.get(a.shape, mc.replicated())),
        opt_shapes)
    tok = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32,
        sharding=mc.sharding(("data", "expert"), "seq"))
    step = make_train_step(mc, cfg, opt)
    return step.lower(params, opt_state, tok, tok).compile()


def _smoke_step(devices, mesh_axes, **cfg_kw):
    """chip_smoke's own 300M train step compiled for described devices."""
    import chip_smoke
    from chainermn_tpu.parallel import MeshConfig

    return _compile_step(
        MeshConfig(devices=devices, **mesh_axes),
        chip_smoke.transformer_config(tiny=False, **cfg_kw),
        chip_smoke.transformer_optimizer(),
        chip_smoke.LM_BATCH, chip_smoke.LM_SEQ)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


def test_300m_train_step_fits_one_chip(topo):
    """The whole train-transformer phase of chip_smoke.py on one
    described chip: the kernel is in the program (not the interpreter,
    not the XLA attention), twice a layer (forward and backward: the
    block's checkpoint keeps the forward's output, so the backward pass
    does not run it again), and arguments + temporaries fit 16 GiB
    under the remat policy the smoke uses."""
    compiled = _smoke_step(topo.devices[:1], dict(data=1))
    assert _flash_kernels(compiled.as_text()) == 2
    need = _device_bytes(compiled)
    assert need < HBM_BYTES, f"{need / 2**30:.1f} GiB > 16 GiB"


@pytest.mark.parametrize("axes,cfg_kw,kernels", [
    (dict(data=4), dict(fsdp=True), 2),
    # the ring's pairs keep rematerialising (TransformerConfig.
    # checkpoint_fn): forward, its remat by the block and by the pair's
    # own checkpoint, and the backward for the visiting pairs; forward,
    # one remat and the backward for the self pair
    (dict(data=1, seq=4), dict(attention="ring"), 7),
], ids=["fsdp-data4", "ring-seq4"])
def test_300m_train_step_four_chips(topo, axes, cfg_kw, kernels):
    """chip_smoke.py --chips 4's two transformer programs on the four
    described devices: the kernel, as often as counted, and the
    collectives are in, and each device's share fits."""
    compiled = _smoke_step(topo.devices, axes, **cfg_kw)
    text = compiled.as_text()
    assert _flash_kernels(text) == kernels
    assert ("all-gather" in text) if "fsdp" in cfg_kw \
        else ("collective-permute" in text)
    assert _device_bytes(compiled) < HBM_BYTES


def _branch_bodies(text):
    """For every ``conditional`` of a compiled program's text, its
    branches' bodies: each the lines of the branch's computation and of
    every computation it calls, as one string."""
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)

    def called(line):
        names = re.findall(
            r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            names += [n.strip().lstrip("%") for n in group.split(",")]
        return names

    def body(name, seen):
        if name in seen or name not in comps:
            return []
        seen.add(name)
        return comps[name] + [l for line in comps[name]
                              for c in called(line) for l in body(c, seen)]

    return [["\n".join(body(b.strip().lstrip("%"), set()))
             for b in group.split(",")]
            for lines in comps.values() for line in lines
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line)]


def _assert_compact_rungs(text, n_rows, rungs, layers):
    """The dropless layers' ladder in a compiled step: a conditional
    forward and one backward a layer (none for the block's remat: the
    laddered section's residuals are its arguments), a branch a rung,
    and a tensor with all ``n_rows`` (token, choice) rows, whatever its
    width, in the last rung's branch alone."""
    import re

    ladders = _branch_bodies(text)
    assert len(ladders) == 2 * layers
    for branches in ladders:
        assert len(branches) == rungs
        full = [len(re.findall(rf"\[{n_rows},\d+\]", b)) for b in branches]
        assert full[-1] > 0 and not any(full[:-1]), full
        # each rung runs the grouped products itself
        assert all(_grouped_sites(b) for b in branches)


# Slow-marked since PR 42: at 67 to 128 s each (415 s of the tier-1 run's
# 8,265 worker-seconds, which ended 36 s inside its 1,470 s limit) these
# four were a twentieth of the suite, and what each guards the benchmark
# repeats on the chip for every PR: a cell whose step no longer fits, or
# has lost a kernel, fails its cell's run (``drivers/*``: the kernel
# check and the compile itself).  ``-m slow`` still runs them, and the
# fifth beside them; tests/tier1_budget.json names them.
@pytest.mark.slow
def test_mellum_cell_step_fits_and_holds_no_capacity_tensor(topo):
    """The benchmark's Mellum cell at its real size (2 x 8,192 tokens,
    four typed layers, 16 of 64 experts held), through the cell's own
    files and its driver's mapping: the flash kernels of both kinds and
    the grouped expert kernels are in the program, no tensor has the
    capacity dispatch's ``(N, E, cap)`` shape, and the compiled step
    needs between 10 and 14.5 GiB of the chip's 16."""
    import re
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import cells, scopes
    from benchmarks.lib.harness import build_optimizer, program_bytes
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell("mellum2-12b-l4-ep4-train-2x8192")
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    compiled = _compile_step(
        MeshConfig(devices=topo.devices[:cell["chips"]], **job["mesh"]),
        pcfg, build_optimizer(cfg["optimizer"]), job["batch"], job["seq"])
    text = compiled.as_text()

    by_scope = {}
    for name, scope in scopes.instruction_scopes(text).items():
        by_scope.setdefault(scope, []).append(name)
    # forward and backward in each layer, and no second forward
    assert _flash_kernels(text, "attn/sliding") == 3 * 2
    assert _flash_kernels(text, "attn/full") == 2
    # three grouped products forward, three recomputed, six backward,
    # in each of four layers, at each of the buffer's two sizes (a
    # quarter of the experts held: half the rows, and all of them)
    assert len(_grouped_sites(text)) == 4 * 12 * 2
    assert "ragged-dot" not in text
    tokens, experts = job["batch"] * job["seq"], cfg["router_experts"]
    _assert_compact_rungs(text, tokens * pcfg.router_top_k, 2, layers=4)
    for dims in set(re.findall(r"\[([\d,]+)\]", text)):
        dims = [int(d) for d in dims.split(",")]
        assert not (len(dims) == 3 and dims[0] == tokens
                    and dims[1] == experts), dims
    gib = program_bytes(compiled) / 2 ** 30
    assert 10 <= gib <= 14.5, f"{gib:.2f} GiB"


@pytest.mark.slow
def test_laguna_cell_step_fits_and_pads_no_heads(topo):
    """The benchmark's Laguna cell at its real size (2 x 8,192 tokens, a
    leading dense layer and four sparse ones, 32 of 256 experts held),
    through the cell's own files and its driver's mapping: each layer's
    flash kernels are there once at its own head count (no tensor of a
    48-head layer has 64 heads), the grouped expert kernels and both
    new scopes are in the program, and the compiled step needs between
    10 and 14.5 GiB of the chip's 16 at the traffic file's
    ``loss_chunk`` (the sizing rule of ISSUE 30)."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import cells, scopes, scopes_mixed
    from benchmarks.lib.harness import build_optimizer, program_bytes
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell("laguna-xs2-l5-ep8-train-seq8192")
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    assert pcfg.blocks_by_position and len(pcfg.leading_layers) == 1
    compiled = _compile_step(
        MeshConfig(devices=topo.devices[:cell["chips"]], **job["mesh"]),
        pcfg, build_optimizer(cfg["optimizer"]), job["batch"], job["seq"])
    text = compiled.as_text()
    # forward and backward in each layer, and no second forward: three
    # sliding layers, the leading full layer and the period's
    assert _flash_kernels(text, "attn/sliding") == 3 * 2
    assert _flash_kernels(text, "attn/full") == 2 * 2
    # an eighth of the experts held: a quarter of the rows, half, all
    assert len(_grouped_sites(text)) == 4 * 12 * 3
    _assert_compact_rungs(text, job["batch"] * job["seq"]
                          * pcfg.router_top_k, 3, layers=4)
    assert set(scopes_mixed.instruction_scopes(text).values()) == {
        "moe/shared", "mlp/dense"}
    # the kernels' operands: (batch x heads, 8192, 128) at 64 and at 48
    for line in text.splitlines():
        if "pallas_call" in line and "tpu_custom_call" in line \
                and "moe/experts" not in line:
            heads = 96 if "attn/full" in line else 128
            assert f"bf16[{heads},8192,128]" in line, line[:200]
    gib = program_bytes(compiled) / 2 ** 30
    assert 10 <= gib <= 14.5, f"{gib:.2f} GiB"



@pytest.mark.slow
def test_kimi_cell_step_fits_with_its_mixers_in_it(topo):
    """The benchmark's Kimi Linear cell at its real size (one sequence
    of 16,384 tokens, a leading KDA layer with the dense MLP and a
    period KDA, KDA, MLA, KDA of sparse ones, 8 of 256 experts held),
    through the cell's own files and its driver's mapping: the MLA
    layer's two flash kernels take keys 192 wide and values 128 as
    they are; each KDA position inverts its chunks' systems in two
    call sites of the op's own kernel (forward and the slab's
    recompute: the block's recompute stops at the scan, whose states
    and output its checkpoint keeps; the solve's VJP has none), 128
    systems on the lanes, and XLA's triangular solve is gone; the same
    two sites make the chunks' pair weights in a kernel and a third,
    backward, makes them again from q, k and G, so no slab step's
    pair-by-pair tensor is in the program; each KDA position's
    convolution is a kernel under ``kda/conv`` at three sites (forward, the block's recompute,
    backward) and pads nothing; the chunked recurrence and
    every new scope are in the program, and the compiled step needs
    between 10 and 14.5 GiB of the chip's 16 at the traffic file's
    ``loss_chunk`` (the sizing rule of ISSUE 32: the first branch,
    ``loss_chunk`` 0)."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import cells, scopes, scopes_hybrid
    from benchmarks.lib.harness import build_optimizer, program_bytes
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell("kimi-linear-l5-ep32-train-tok16384")
    assert (job["batch"], job["seq"], job["loss_chunk"]) == (1, 16384, 0)
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    assert pcfg.blocks_by_position and len(pcfg.leading_layers) == 1
    compiled = _compile_step(
        MeshConfig(devices=topo.devices[:cell["chips"]], **job["mesh"]),
        pcfg, build_optimizer(cfg["optimizer"]), job["batch"], job["seq"])
    text = compiled.as_text()
    # forward and backward of the one MLA layer, and no second forward
    assert _flash_kernels(text, "attn/mla") == 2
    kernels = [line for line in text.splitlines()
               if "pallas_call" in line and "tpu_custom_call" in line
               and "moe/experts" not in line]
    inversions = [line for line in kernels if "kda.solve" in line]
    pairs = [line for line in kernels if "kda.pairs" in line]
    # the leading layer and three of the period's four: forward and the
    # slab's recompute, and no site in the solve's VJP; the pair
    # weights' kernel at the same two and its VJP's once
    assert len(inversions) == 4 * 2 and len(pairs) == 4 * (2 + 1)
    convs = _conv_sites(text, "kda/conv")
    assert len(convs) == 4 * 3
    assert len(kernels) == 2 + 4 * 2 + 4 * 3 + 4 * 3
    assert all("kda/scan" in line for line in inversions + pairs)
    assert "InvertDiagBlocksLowerTriangular" not in text
    for line in inversions:
        # a slab step's 1 x 32 x 4 systems of 64 x 64, a system a lane
        assert "= f32[64,64,128]{2,1,0" in line, line[:300]
    # a slab step's 128 blocks: A and A' out, or dq, dk and dG
    assert sum(" = (f32[128,64,64]{" in line for line in pairs) == 4 * 2
    assert sum(" = (f32[128,4,16,128]{" in line for line in pairs) == 4
    for line in kernels:
        # 32 heads of one sequence: q and k 192 wide, v and o 128
        assert line in inversions or line in pairs or line in convs or (
            "bf16[32,16384,192]" in line
            and "bf16[32,16384,128]" in line), line[:300]
    # what the jnp form stored a slab step: col and kcol, pair
    assert "f32[32,4,4,64,128]" not in text
    assert "4,4,16,16,128]" not in text
    assert set(scopes_hybrid.instruction_scopes(text).values()) == {
        "kda/conv", "kda/scan", "kda/gate", "mla/latent"}
    by_scope = set(scopes.instruction_scopes(text).values())
    assert {"attn/kda", "attn/mla", "moe/experts"} <= by_scope
    gib = program_bytes(compiled) / 2 ** 30
    assert 10 <= gib <= 14.5, f"{gib:.2f} GiB"


@pytest.mark.slow
def test_nemotron_cell_step_fits_with_its_scan_in_xla(topo):
    """The benchmark's Nemotron-H cell at its real size (two sequences
    of 8,192 tokens, nine one-part layers MEMEM*EME, 8 of 128 ReLU^2
    experts held), through the cell's own files and its driver's
    mapping: the one attention layer's two flash kernels and, a
    Mamba-2 position, the convolution's kernel under ``ssm/conv`` at
    three sites (forward, the block's recompute, backward; nothing
    padded) are the step's only custom calls (the state-space scan is
    XLA's batched products: importing ``ops/ssd.py`` brings no kernel),
    K and V reach the flash kernels copied out to the 32 query heads,
    the held experts' width of 1,856 is padded to the 1,920 of whole
    lane tiles, every new scope is in the program, and the compiled
    step needs between 12 and 14.5 GiB of the chip's 16 at the traffic
    file's ``loss_chunk`` (the sizing rule of
    ISSUE 40: the first branch, ``loss_chunk`` 0)."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import cells
    from benchmarks.lib.harness import build_optimizer, program_bytes
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell(
        "nemotron-twotower-l9-ep16-train-ssm-2x8192")
    assert (job["batch"], job["seq"], job["loss_chunk"]) == (2, 8192, 0)
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    assert pcfg.blocks_by_position and pcfg.parts_alone
    compiled = _compile_step(
        MeshConfig(devices=topo.devices[:cell["chips"]], **job["mesh"]),
        pcfg, build_optimizer(cfg["optimizer"]), job["batch"], job["seq"])
    text = compiled.as_text()
    # forward and backward of the one attention layer; the convolution
    # of each of the four Mamba-2 positions; no other kernel
    assert _flash_kernels(text, "attn/full") == 2
    kernels = [line for line in text.splitlines()
               if "pallas_call" in line and "tpu_custom_call" in line
               and "moe/experts" not in line]
    convs = _conv_sites(text, "ssm/conv")
    assert len(convs) == 4 * 3 and len(kernels) == 2 + 4 * 3
    assert "ssm/scan" not in "".join(kernels)
    for line in kernels:
        # 32 query heads of two sequences, keys and values copied out
        assert line in convs or "bf16[64,8192,128]" in line, line[:300]
    for scope in ("attn/mamba2", "ssm/conv", "ssm/gate", "ssm/scan",
                  "ssm.intra", "ssm.states", "ssm.carry", "ssm.inter",
                  "moe/shared", "moe/route"):
        assert scope in text, scope
    assert "attn.rope" not in text      # nothing is rotated
    # the held experts meet the grouped kernels at whole lane tiles
    assert "bf16[8,2688,1920]" in text and "bf16[8,1920,2688]" in text
    gib = program_bytes(compiled) / 2 ** 30
    assert 12 <= gib <= 14.5, f"{gib:.2f} GiB"


@pytest.mark.slow
def test_qwen3_next_cell_step_fits_with_one_kernel_in_its_delta_rule(topo):
    """The benchmark's Qwen3-Next cell at its real size (one sequence of
    16,384 tokens, one period: Gated DeltaNet x 3, gated attention at a
    head width of 256; 32 of 512 experts held), through the cell's own
    files and its driver's mapping: the attention layer's two flash
    kernels (the backward at 1,024 x 1,024 like every unwindowed layer:
    it asks the compiler for the VMEM its shapes need, 58 MiB at 256 +
    256 over 16,384 queries) and, a linear
    layer, the inversion kernel of ``ops/kda.py`` twice (forward and
    the slab's recompute: the block's checkpoint keeps the scan's
    states and output, so its recompute stops there) and the convolution's
    kernel under ``gdn/conv`` three times (forward, the block's
    recompute, backward; nothing padded) are the step's only custom
    calls -- no pair kernel: the scalar decay's pair weights are XLA's
    -- every new scope is in the program, and the compiled step
    needs between 12 and 14.5 GiB of the chip's 16 at the traffic
    file's ``loss_chunk`` (the sizing rule of ISSUE 42: the first
    branch).  Slow from the start: the benchmark's run repeats it."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import cells
    from benchmarks.lib.harness import build_optimizer, program_bytes
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell("qwen3-next-l4-ep16-train-gdn-longseq")
    assert (job["batch"], job["seq"], job["loss_chunk"]) == (1, 16384, 0)
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    assert pcfg.blocks_by_position and pcfg.mixers == ["gdn"]
    compiled = _compile_step(
        MeshConfig(devices=topo.devices[:cell["chips"]], **job["mesh"]),
        pcfg, build_optimizer(cfg["optimizer"]), job["batch"], job["seq"])
    text = compiled.as_text()
    assert _flash_kernels(text, "attn/full") == 2
    kernels = [line for line in text.splitlines()
               if "pallas_call" in line and "tpu_custom_call" in line
               and "moe/experts" not in line]
    assert len(_conv_sites(text, "gdn/conv")) == 3 * 3
    assert len(kernels) == 2 + 3 * 3 + 3 * 2
    assert sum("kda.solve" in line for line in kernels) == 6
    assert "kda.pairs" not in text
    for scope in ("attn/gdn", "gdn/conv", "gdn/gate", "gdn/scan",
                  "gdn.pairs", "gdn.intra", "gdn.inter", "attn.qk_norm",
                  "attn.gate", "attn.rope", "moe/shared", "moe/route"):
        assert scope in text, scope
    gib = program_bytes(compiled) / 2 ** 30
    assert 12 <= gib <= 14.5, f"{gib:.2f} GiB"


@pytest.mark.slow
def test_lfm2_cell_step_fits_with_the_short_convolutions_kernels_in_it(topo):
    """The benchmark's LFM2 cell at its real size (four sequences of
    8,192 tokens; the convolution layer with the dense SwiGLU, then
    attention, convolution x 3, all sparse; 8 of 64 experts held),
    through the cell's own files and its driver's mapping: the
    attention layer's two flash kernels, ``gated_short_conv``'s kernel
    under ``shortconv/conv`` three times a convolution layer (forward,
    the block's recompute, backward; nothing padded under the scope)
    and the grouped products' kernels under ``moe/experts`` are the
    step's only custom calls, every new scope is in the program, and
    the compiled step needs between 9 and 14.5 GiB of the chip's 16 at
    the traffic file's ``loss_chunk`` (the sizing rule of ISSUE 49: the
    first branch, 10.34 GiB).  Slow from the start: the benchmark's run
    repeats it."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import cells
    from benchmarks.lib.harness import build_optimizer, program_bytes
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell("lfm2-24b-l5-ep8-train-conv-seq8192")
    assert (job["batch"], job["seq"], job["loss_chunk"]) == (4, 8192, 0)
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    assert pcfg.blocks_by_position and pcfg.mixers == ["shortconv"]
    compiled = _compile_step(
        MeshConfig(devices=topo.devices[:cell["chips"]], **job["mesh"]),
        pcfg, build_optimizer(cfg["optimizer"]), job["batch"], job["seq"])
    text = compiled.as_text()
    assert _flash_kernels(text, "attn/full") == 2
    kernels = [line for line in text.splitlines()
               if "pallas_call" in line and "tpu_custom_call" in line
               and "moe/experts" not in line]
    assert len(_conv_sites(text, "shortconv/conv")) == 4 * 3
    assert len(kernels) == 2 + 4 * 3
    assert len(_grouped_sites(text)) > 0 and "ragged-dot" not in text
    for scope in ("attn/conv", "attn/full", "shortconv/conv", "attn.qkv",
                  "attn.out", "attn.qk_norm", "attn.rope", "mlp/dense",
                  "moe/route"):
        assert scope in text, scope
    assert "moe/shared" not in text
    gib = program_bytes(compiled) / 2 ** 30
    assert 9 <= gib <= 14.5, f"{gib:.2f} GiB"
