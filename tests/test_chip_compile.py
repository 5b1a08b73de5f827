"""The main path's Pallas kernels, compiled by the chip's own compiler.

Every other kernel test runs in pallas interpret mode on the CPU mesh, which
accepts block shapes and VMEM footprints that Mosaic refuses. Here each
kernel is lowered with interpret=False for a DESCRIBED v5e:2x2 device (no
chip attached, nothing runs) at GPT-2-medium widths, so a tiling or VMEM
refusal fails tier-1 instead of the first chip call. The same kernels are
then compiled on a Mesh of the four described devices — per shard, the way
kernels/partition.py places them — because the chip's compiler refuses a
Mosaic call that GSPMD would have to partition, and only a multi-device
compile shows that. Last, XLA's own memory analysis of a --remat train step,
from the compiler whose answer matters.

The topology is described inside a module-scoped fixture of THIS file only:
one process at a time may load the TPU library, so nothing here may happen
at import, in a skipif/parametrize argument, in conftest.py or in a child
process (the xdist workers each import every test file).
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # chip_smoke

KERNEL_MODULES = ("flash_attention", "dequant_attention", "ssd_scan",
                  "kda_scan", "retention_step", "moe_step", "mamba2_step",
                  "sparse_attend_step", "sparse_attend_chunk", "moe_rows",
                  "selective_scan", "head_turn")

# GPT-2 medium: batch 8, 16 heads of 64, seq 1024
B, H, S, D = 8, 16, 1024, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable can be written to the persistent cache
    # but not read back without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2x2(topo):
    """The four described chips as the {data:2, model:2} mesh of
    `chip_smoke.py --chips 4`."""
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


@pytest.fixture
def described_devices(topo, monkeypatch):
    """FFModel.compile builds its mesh (and detects its chip) from
    jax.devices(): hand it the described chips for the length of a test.
    jax.default_backend() still says cpu — nothing can be placed or run,
    the step is compiled from shapes."""
    def hand_out(n):
        devs = list(topo.devices)[:n]
        monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)

    return hand_out


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels key interpret mode on jax.default_backend(), which still
    says cpu here: force the Mosaic path from the test."""
    import importlib

    for name in KERNEL_MODULES:
        mod = importlib.import_module(f"flexflow_tpu.kernels.{name}")
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered through Mosaic"
    return compiled


def test_device_kind_is_in_the_peaks_table(topo):
    from flexflow_tpu.parallel.machine import CHIP_PRESETS, chip_for_device_kind

    kind = topo.devices[0].device_kind
    assert chip_for_device_kind(kind) in CHIP_PRESETS


def test_flash_attention_forward(one_chip, mosaic):
    from flexflow_tpu.kernels.flash_attention import flash_attention

    qkv = ((B, H, S, D), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             one_chip, qkv, qkv, qkv)


def test_flash_attention_backward(one_chip, mosaic):
    """dq and dk/dv kernels: grad w.r.t. all three operands."""
    from flexflow_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    qkv = ((B, H, S, D), jnp.bfloat16)
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        one_chip, qkv, qkv, qkv)
    # forward (residuals) + dq + dkv
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("groups, chunk", [(1, 256), (8, 128)],
                         ids=["granite", "nemotron"])
def test_mamba_scan_at_the_served_widths(one_chip, mosaic, groups, chunk):
    """`ff_ssd_chunk_scan` as the two hybrid cells' prefill waves call it
    (16 rows of 1024, 128 heads of 64, a state of 128; one B/C group at
    chunk 256, eight at 128), the gated entry reading z out of the whole `[z
    | xBC | dt]`, and the plain scan: 16 heads a grid step, a group's state
    resident over its tiles (8192 lanes of it in f32 for one group, in
    eight sub-blocks), has to pass Mosaic and its VMEM, not only interpret
    mode, and nothing the size of the wave in f32 may be left around the
    gated kernel."""
    from flexflow_tpu.ops import ssm_ops

    rows, seq, heads, hd, n = 16, 1024, 128, 64, 128
    width = 2 * heads * hd + 2 * groups * n + heads
    f32 = jnp.float32
    scan = [((rows, seq, heads, hd), jnp.bfloat16), ((rows, seq, heads), f32),
            ((heads,), f32), ((rows, seq, groups, n), jnp.bfloat16),
            ((rows, seq, groups, n), jnp.bfloat16)]
    assert ssm_ops.scan_path(*(jax.ShapeDtypeStruct(*scan[i]) for i in (0, 3)),
                             chunk) \
        == {"path": "kernel", "tile": chunk, "head_block": 16}
    _compile(lambda *t: ssm_ops.ssd_scan(*t, chunk), one_chip, *scan)
    u, dt, a, bm, cm = scan
    gated = _compile(
        lambda u, z, *t: ssm_ops.mixer_scan(u, z, *t, chunk, 1e-5), one_chip,
        u, ((rows, seq, width), jnp.bfloat16), dt, a, bm, cm,
        ((heads,), f32), ((heads * hd,), jnp.bfloat16))
    assert gated.memory_analysis().temp_size_in_bytes < rows * seq * heads * hd * 4


@pytest.mark.parametrize("groups", [1, 8], ids=["granite", "nemotron"])
def test_mamba_step_at_the_served_widths(one_chip, mosaic, groups):
    """`ff_mamba2_step` as the two hybrid cells' decode steps call it (16
    slots of 128 heads of 64, a state of 128; one B/C group or eight): 64
    heads' 2.1 MB a grid step, in and out one buffer, has to pass
    Mosaic and the VMEM it states, not only interpret mode, and the slot
    array must be updated where it lies: no second one among the
    temporaries."""
    from flexflow_tpu.ops import ssm_ops

    slots, heads, hd, n = 16, 128, 64, 128
    f32 = jnp.float32
    path = ssm_ops.step_path(heads, hd, n, groups)
    assert path == {"path": "kernel", "head_block": 64, "groups": groups}
    bc = (slots, n) if groups == 1 else (slots, groups, n)
    shapes = [((slots, heads, hd, n), f32), ((slots, heads), f32),
              ((heads,), f32), ((slots, heads, hd), f32), (bc, f32),
              (bc, f32), ((slots,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in shapes]
    compiled = jax.jit(lambda *t: ssm_ops.ssm_step(*t, path),
                       donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ff_mamba2_step" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024


def test_kda_scan_at_the_served_widths(one_chip, mosaic):
    """`ff_kda_chunk_scan` as Ling's prefill wave calls it (16 rows of 1024,
    32 heads of 128, bfloat16; eight heads of straight-line code a grid
    step, their `[8, 128, 128]` f32 state resident over a row's tiles), the
    layer's entry with the unit vectors and the gated head norm on the tile
    and the plain scan: it has to pass Mosaic and its VMEM, not only
    interpret mode; nothing the size of the wave's `[b, L, H, D]` in f32 may
    be left around the layer's call, and the call is an instruction of its
    own under the scope that `kda_scan_roofline` asks for."""
    from flexflow_tpu import attribution
    from flexflow_tpu.ops import kda_ops

    rows, seq, heads, hd = 16, 1024, 32, 128
    bf, f32 = jnp.bfloat16, jnp.float32
    wave = ((rows, seq, heads, hd), bf)
    assert kda_ops.scan_path(jax.ShapeDtypeStruct(*wave), -5.0) \
        == {"path": "kernel", "tile": 128, "head_block": 8}
    _compile(lambda *t: kda_ops.kda_chunk_scan(*t, -5.0), one_chip,
             wave, wave, wave, ((rows, seq, heads, hd), f32),
             ((rows, seq, heads), f32))
    inner = heads * hd
    mixer = _compile(
        lambda *t: kda_ops.kda_mixer_scan(*t, heads, -5.0, 1e-6), one_chip,
        ((rows, seq, 3 * inner), bf), ((rows, seq, inner), bf),
        ((rows, seq, inner), f32), ((rows, seq, heads), f32), ((hd,), bf))
    assert mixer.memory_analysis().temp_size_in_bytes < rows * seq * inner * 4
    inside = attribution.instructions_in_scope(mixer.as_text(),
                                               kda_ops.SCAN_SCOPE)
    assert any(name.startswith("ff_kda_chunk_scan") for name in inside)


@pytest.mark.parametrize("bq,bk", [(256, 256), (256, 128), (128, 256)])
def test_flash_attention_causal_tiles(one_chip, mosaic, bq, bk):
    """The three kernels at the tiles the causal rule takes at GPT-2
    medium's shape, and at unequal q and k tiles both ways: the static
    schedule under the diagonal (slices at tile multiples, a masked product
    per row of tiles) has to pass Mosaic, not only interpret mode; through
    the swapped entry and two heads a 128-lane block (PR 63: each head's 64
    lanes a static slice of the tile), `lse` and `delta` as rows."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    if bq == bk:
        plan = fa.tile_plan(S, S, D, 2, True)
        for kern in fa.KERNELS:
            assert (plan[kern]["flash_tile_q"], plan[kern]["flash_tile_k"]) \
                == (bq, bk)
            assert plan[kern]["flash_tiles_visited"] \
                < plan[kern]["flash_tiles_total"]
    vec = ((B, H, 1, S), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    assert fa.entry_of(D, H, H) == "two_heads"
    for heads, qkv in ((0, ((B, H, S, D), jnp.bfloat16)),
                       (H, ((B, S, H * D), jnp.bfloat16))):
        form = {"heads": heads}
        _compile(lambda q, k, v: fa._fwd_call(q, k, v, True, scale, bq, bk,
                                              **form),
                 one_chip, qkv, qkv, qkv)
        _compile(lambda *a: fa._dq_call(*a, True, scale, bq, bk, **form),
                 one_chip, qkv, qkv, qkv, qkv, qkv, vec)
        _compile(lambda *a: fa._dkv_call(*a, True, scale, bq, bk, **form),
                 one_chip, qkv, qkv, qkv, qkv, vec, vec)


@pytest.mark.parametrize("window,entry", [
    (2048, "merged"), (0, "merged"), (2048, "swapped")])
def test_flash_attention_under_a_window_at_8k(one_chip, mosaic, window, entry):
    """Trinity-Mini's attention at the trained shape: two sequences of 8192,
    32 query heads over 4 K/V heads of 128 (read through the block index),
    the three kernels under a window of 2048 and without one, as the step
    calls them (PR 63: operands `[b, s, h * d]`, the head the lane block)
    and through the swapped entry. The backward's whole-sequence operands
    pass Mosaic's default VMEM scope, so the calls ask for their own. No
    statistic is `[.., 8192, 1]` and none is relaid: `lse` and `delta` are
    `f32[2,32,1,8192]`, 2.1 MB as they lie."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    b, h, kv, s, d = 2, 32, 4, 8192, 128
    assert fa.entry_of(d, h, kv) == "merged"
    attend = fa.flash_attention if entry == "swapped" else \
        lambda q, k, v, **kw: fa.flash_attention_merged(q, k, v, h, **kw)

    def grads(q, k, v, ct):
        return jax.grad(lambda q, k, v: jnp.sum(attend(
            q, k, v, causal=True, window=window).astype(jnp.float32) * ct),
            (0, 1, 2))(q, k, v)

    def shape(heads):
        return (b, heads, s, d) if entry == "swapped" else (b, s, heads * d)

    compiled = _compile(grads, one_chip, (shape(h), jnp.bfloat16),
                        (shape(kv), jnp.bfloat16), (shape(kv), jnp.bfloat16),
                        (shape(h), jnp.float32))
    text = compiled.as_text()
    for kernel in ("fwd", "dq", "dkv"):
        assert f"ff_flash_attention_{kernel}" in text
    assert not re.search(r"\[\d+,\d+,8192,8192\]", text)
    assert "f32[2,32,1,8192]{3,2,1,0:T(1,128)" in text
    assert not re.search(r"f32\[[\d,]*8192,1\]", text)
    plan = fa.tile_plan(s, s, d, 2, True, window)
    if window:      # 16 grid steps of 512, at most 5 key blocks a step: 70 of 136
        assert plan["fwd"]["flash_tiles_visited"] \
            < 0.55 * fa.tile_plan(s, s, d, 2, True)["fwd"]["flash_tiles_visited"]


def test_head_turn_at_the_trained_widths(one_chip, mosaic):
    """PR 63: the head norm and the rotation on the merged axis
    (kernels/head_turn.py) at Trinity-Mini's q and k, forward and backward
    through Mosaic: a lane roll by half a head, a lane reduction a head,
    d gamma summed over a grid axis."""
    from flexflow_tpu.kernels.head_turn import head_turn

    b, s, d = 2, 8192, 128
    for heads, tables in ((32, True), (4, False)):
        def grads(x, gamma, cos, sin, ct):
            return jax.grad(lambda x, gamma: jnp.sum(head_turn(
                x, gamma, cos if tables else None, sin if tables else None,
                heads, 1e-6).astype(jnp.float32) * ct), (0, 1))(x, gamma)

        text = _compile(grads, one_chip, ((b, s, heads * d), jnp.bfloat16),
                        ((d,), jnp.bfloat16), ((b, s, d), jnp.float32),
                        ((b, s, d), jnp.float32),
                        ((b, s, heads * d), jnp.float32)).as_text()
        assert "ff_head_turn_bwd" in text


@pytest.mark.parametrize("q_tokens", [1, 5])
def test_dequant_decode_attention(one_chip, mosaic, q_tokens):
    """The serving engine's int8 geometry: [slots, L, 16, 64] gathered
    context with per-(entry, head) scales; 1 query token for plain decode,
    K+1 for the speculative-verify window."""
    from flexflow_tpu.kernels.dequant_attention import dequant_decode_attention

    slots, L = 8, 1024
    _compile(dequant_decode_attention, one_chip,
             ((slots, q_tokens, H, D), jnp.bfloat16),
             ((slots, L, H, D), jnp.int8), ((slots, L, H), jnp.float32),
             ((slots, L, H, D), jnp.int8), ((slots, L, H), jnp.float32),
             ((slots,), jnp.int32))


# ------------------------------------------------ on a mesh of four devices
def _compile_on(mesh, fn, *args):
    """args: (shape, dtype, PartitionSpec) on `mesh`."""
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, spec))
              for s, dt, spec in args]
    return jax.jit(fn).lower(*shapes).compile()


def test_flash_attention_per_shard_on_the_mesh(mesh2x2, mosaic):
    """Forward and both backward kernels under shard_map with the specs the
    attention op derives from a {data, model} strategy: batch on data, heads
    on model. (b, s, h, d) is the op's own layout."""
    from flexflow_tpu.kernels.flash_attention import flash_attention_qkv
    from flexflow_tpu.kernels.partition import per_shard

    spec = P("data", None, "model", None)
    attn = per_shard(lambda q, k, v: flash_attention_qkv(q, k, v, causal=True),
                     mesh2x2, (spec, spec, spec), spec)

    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    qkv = ((B, S, H, D), jnp.bfloat16, spec)
    text = _compile_on(mesh2x2, jax.grad(loss, argnums=(0, 1, 2)),
                       qkv, qkv, qkv).as_text()
    assert text.count("tpu_custom_call") >= 3


def test_mosaic_kernel_outside_shard_map_is_refused_on_the_mesh(mesh2x2,
                                                                mosaic):
    """Why partition.py exists: the same kernel handed to GSPMD on sharded
    operands does not compile. If this ever passes, per_shard can go."""
    from flexflow_tpu.kernels.flash_attention import flash_attention_qkv

    qkv = ((B, S, H, D), jnp.bfloat16, P("data", None, "model", None))
    with pytest.raises(Exception, match="Mosaic kernels cannot be "
                                        "automatically partitioned"):
        _compile_on(mesh2x2,
                    lambda q, k, v: flash_attention_qkv(q, k, v, causal=True),
                    qkv, qkv, qkv)


def test_dequant_decode_attention_per_shard_on_the_mesh(mesh2x2, mosaic):
    """Decode slots stay replicated, heads split on model — the specs
    ops/attention_ops.py passes for the int8 KV cache."""
    import functools

    from flexflow_tpu.kernels.dequant_attention import dequant_decode_attention
    from flexflow_tpu.kernels.partition import per_shard

    slots, L = 8, 1024
    spec, sspec = P(None, None, "model", None), P(None, None, "model")
    fn = per_shard(functools.partial(dequant_decode_attention, scale=0.125),
                   mesh2x2, (spec, spec, sspec, spec, sspec, P()), spec)
    text = _compile_on(
        mesh2x2, fn,
        ((slots, 1, H, D), jnp.bfloat16, spec),
        ((slots, L, H, D), jnp.int8, spec), ((slots, L, H), jnp.float32, sspec),
        ((slots, L, H, D), jnp.int8, spec), ((slots, L, H), jnp.float32, sspec),
        ((slots,), jnp.int32, P())).as_text()
    assert "tpu_custom_call" in text


def _train_step_shapes(cm, label_shape):
    """The jitted train step's arguments as shapes with cm's own shardings
    (a described device holds no array)."""
    def sds(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    pshapes, pshards = cm._param_templates()
    params = jax.tree_util.tree_map(sds, pshapes, pshards)
    opt = jax.tree_util.tree_map(sds, jax.eval_shape(cm.tx.init, pshapes),
                                 cm._opt_sh)
    ins = [jax.ShapeDtypeStruct(t.spec.shape, t.spec.dtype.jnp_dtype,
                                sharding=cm.input_sharding(t))
           for t in cm.model.input_tensors]
    label = jax.ShapeDtypeStruct(label_shape, jnp.int32,
                                 sharding=cm.label_sharding(label_shape))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(cm.mesh, P()))
    return params, opt, {}, ins, label, key


_SMOKE_STEPS = {}


def _smoke_step(cs, described_devices, chips, **cfg_kw):
    """chip_smoke's GPT-2 medium at depth 2, default config, compiled for
    `chips` described devices: (cm, compiled step). One compile per
    configuration for the whole file."""
    key = (chips, repr(sorted(cfg_kw.items())))
    if key not in _SMOKE_STEPS:
        described_devices(chips)
        gcfg = cs.gpt2_medium()
        gcfg.layers = 2
        _, cm, _, _ = cs._build(gcfg, B, 0, init=False, **cfg_kw)
        _SMOKE_STEPS[key] = cm, cm.train_step.lower(
            *_train_step_shapes(cm, (B, gcfg.seq))).compile()
    return _SMOKE_STEPS[key]


SEARCHED_2X2 = dict(search_budget=32, mesh_shape={"data": 2, "model": 2})


def test_searched_train_step_on_the_mesh(described_devices, mosaic):
    """The whole sharded step through the normal entry points: chip_smoke's
    GPT-2 medium at depth 2, searched on {data:2, model:2} as its --chips 4
    leg is. Every kernel call site of the lowering must sit inside per_shard
    or the chip's compiler refuses the program here."""
    import chip_smoke as cs

    cm, compiled = _smoke_step(cs, described_devices, 4, zero_sharding="off",
                               **SEARCHED_2X2)
    assert cm.strategy.name.startswith("unity")
    text = compiled.as_text()
    assert cs.kernels_in(text)["flash_attention"] >= 3 * 2
    assert sum(cs.collectives_in(text).values()) > 0


def test_the_loss_has_one_path_at_a_lane_aligned_vocab(described_devices,
                                                       mosaic):
    """GPT-2's step at a vocabulary of 50304, a multiple of 128 (rows a
    multiple of 8, bf16): the shapes at which a fused cross-entropy kernel
    used to be chosen. The loss is the optax form whatever the shapes are:
    the log-softmax's own operations under `ff.loss`, the scope
    `step_loss_device_ms.train` joins on, and no kernel call there."""
    import chip_smoke as cs
    from flexflow_tpu.attribution import LOSS_SCOPE

    described_devices(1)
    gcfg = cs.gpt2_medium()
    gcfg.layers, gcfg.vocab = 1, 50304
    _, cm, _, _ = cs._build(gcfg, B, 0, init=False, mesh_shape={"data": 1})
    text = cm.train_step.lower(
        *_train_step_shapes(cm, (B, gcfg.seq))).as_text(debug_info=True)
    # the name stacks of the lowered step's operations
    names = re.findall(r'loc\("([^"]+)"', text)
    assert sum("ff_flash_attention" in n for n in names) == 3  # fwd, dq, dkv
    loss = [n for n in names if f"({LOSS_SCOPE})" in n]
    for op in ("reduce_max", "exp", "log"):
        assert f"jit(train_step)/jvp({LOSS_SCOPE})/{op}" in loss, op
    assert not any("pallas_call" in n for n in loss), loss
    assert "ff_fused_ce" not in text


def _elements(ty):
    """Element count of the (first) array of an HLO result type, or None."""
    shape = re.search(r"\[([\d,]+)\]", ty)
    return None if shape is None else int(
        np.prod([int(d) for d in shape.group(1).split(",")]))


def _whole_weight_relayouts(text, cm):
    """`copy` / `reshape` / `transpose` of the compiled step's entry
    computation whose f32 result is as large as a whole weight matrix (the
    smallest per-device shard of a parameter leaf of two or more dims, as
    the parameters or the moments lie). The forward and backward hold their
    weights in bf16, so an f32 array of that size is a parameter, a moment,
    a gradient or an update: the optimizer's. `copy-start` is let through:
    the compiler's own prefetch into its alternate memory, no relayout."""
    pshapes, pshards = cm._param_templates()
    sizes = [int(np.prod(sh.shard_shape(s.shape)))
             for shards in (pshards, cm._moment_sh)
             for s, sh in zip(jax.tree_util.tree_leaves(pshapes),
                              jax.tree_util.tree_leaves(shards))
             if len(s.shape) >= 2]
    return [(op, ty) for op, ty in _entry_ops(text)
            if op in ("copy", "reshape", "transpose")
            and ty.startswith("f32[") and _elements(ty) >= min(sizes)]


def test_the_update_passes_over_each_leaf_as_it_lies(described_devices,
                                                     mosaic):
    """One chip, the default training step: the optimizer update is the
    optax chain fused by XLA, one pass per leaf in place. No Mosaic call of
    an update kernel, and no whole-weight f32 `reshape` / `copy` /
    `transpose` anywhere in the step (with the per-leaf Pallas kernel this
    program held 108 of them at depth 2: seven around each call, the
    `reshape` + `copy` 36 ms of GPT-2 medium's 244 ms step), and every
    parameter and moment aliased onto an output."""
    import chip_smoke as cs

    cm, compiled = _smoke_step(cs, described_devices, 1)
    text = compiled.as_text()
    assert "ff_fused_optim" not in text
    assert cs.kernels_in(text)["flash_attention"] >= 6
    assert _whole_weight_relayouts(text, cm) == []
    # PR 63: the flash kernels read q, k, v and write o as the projections
    # hold them (two heads of 64 a 128-lane block): no q-sized `copy` /
    # `transpose` under an attention layer (8 a layer before), and no
    # `[b, h, s, 1]` statistic in the step (`lse`, `delta`: `f32[8,16,1,1024]`)
    from flexflow_tpu import attribution

    op_types = {l.name: l.op_type.value for l in cm.model.layers}
    assert attribution.step_passes(text, op_types) \
        == {"flash_fwd_passes": 1.0, "flash_relayouts": 0.0}
    assert not re.search(r"f32\[\d+,\d+,1024,1\]", text)
    pshapes, _ = cm._param_templates()
    held = 3 * sum(s.size * s.dtype.itemsize
                   for s in jax.tree_util.tree_leaves(pshapes))
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.parametrize("zero,collectives", [
    pytest.param("off", {"all-reduce": 28, "all-gather": 27}, id="searched"),
    pytest.param("zero1", {"all-reduce": 37, "all-gather": 73,
                           "collective-permute": 9}, id="searched-zero1"),
])
def test_the_update_on_the_mesh_adds_no_collective(described_devices, mosaic,
                                                   zero, collectives):
    """The same on {data:2, model:2}, searched, without and with ZeRO-1:
    GSPMD partitions the fused update by the moments' own specs (the
    per-leaf kernel ran under 37 `shard_map`s here). `collectives` is what
    the step held WITH the kernel (PR 31's parent, this compile): the
    optax path may not add one. Under ZeRO-1 the moments come back in
    `moment_sh`, split over `data` where the parameters are not."""
    import chip_smoke as cs

    cm, compiled = _smoke_step(cs, described_devices, 4, zero_sharding=zero,
                               **SEARCHED_2X2)
    assert cm.strategy.name.startswith("unity")
    text = compiled.as_text()
    assert "ff_fused_optim" not in text
    assert _whole_weight_relayouts(text, cm) == []
    got = cs.collectives_in(text)
    assert all(got[op] <= collectives.get(op, 0) for op in got), got
    if zero == "off":       # the step pins its state's layout under ZeRO only
        return
    out_params, out_opt = compiled.output_shardings[:2]
    mu = out_opt[0].mu                 # optax.adam: (ScaleByAdamState, Empty)
    leaves = jax.tree_util.tree_leaves
    split_more = 0
    for got_sh, want_sh, param_sh, s in zip(
            leaves(mu), leaves(cm._moment_sh), leaves(out_params),
            leaves(cm._param_templates()[0])):
        assert got_sh.is_equivalent_to(want_sh, len(s.shape)), (got_sh, want_sh)
        split_more += (got_sh.shard_shape(s.shape)
                       != param_sh.shard_shape(s.shape))
    assert split_more > 0


def test_remat_shrinks_the_compiled_steps_temp_memory(described_devices):
    """Per-layer jax.checkpoint shrinks the live temp buffers of the
    COMPILED train step of a chain of eight dense layers: asked of the
    chip's compiler (XLA:CPU reports the same figure both ways). At 32 MB
    an activation: the compiler keeps a chain of 1 MB ones out of its
    temporaries altogether, and reports 2 644 992 B with and without."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.losses import LossType

    described_devices(1)
    batch, hidden, layers = 8192, 1024, 8
    temp = {}
    for remat in (False, True):
        m = FFModel(FFConfig(batch_size=batch, only_data_parallel=True,
                             remat=remat, seed=3, strategy_cache=False,
                             log_level="warning"))
        h = m.create_tensor([batch, hidden], name="x")
        for i in range(layers):
            h = m.dense(h, hidden, activation="gelu", name=f"blk{i}")
        m.dense(h, 64, name="head")
        cm = m.compile(SGDOptimizer(lr=0.01),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
        compiled = cm.train_step.lower(
            *_train_step_shapes(cm, (batch,))).compile()
        temp[remat] = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp[True] < 0.7 * temp[False], temp


def _described_engine(cell_name, described_devices, monkeypatch, one_chip):
    """A benchmark cell's serving engine through the normal entry points on
    one described chip, with its parameters and cache state as shapes."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from families import family_of
    from harness import manifest as mf

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.compiler.compile import build_init_fn
    from flexflow_tpu.core.graph import topo_order
    from flexflow_tpu.serving import compile_serving

    described_devices(1)
    # a described device holds no array: the state manager's zeros stay put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    cell = mf.load_cell(mf.load_manifest(), cell_name)
    slots = cell.system["max_batch_slots"]
    model = FFModel(FFConfig(batch_size=slots, seed=3, strategy_cache=False,
                             log_level="warning", **cell.system["ffconfig"]))
    g = family_of(cell.config).build(model, cell.config, slots)
    eng = compile_serving(model, max_batch_slots=slots,
                          max_decode_len=cell.system["max_decode_len"],
                          kv_page_size=cell.system["kv_page_size"])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    init = build_init_fn(topo_order(eng.decode_model.layers),
                         model._initializer_overrides)
    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(init, jax.random.PRNGKey(0)))
    state = jax.tree_util.tree_map(sds, eng.kv.state)
    return eng, g, params, state


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _assert_mixers_step_in_place(decode, mixers: int):
    """Every Mamba-2 layer's recurrence is one `ff_mamba2_step` call, and a
    slot array `f32[16,128,64,128]` is nowhere in the step but as a
    parameter, that call's result and the program's own: no copy, slice or
    fusion of that size (the XLA form's `multiply_reduce_fusion` a layer,
    and what the compiler staged around it, are gone)."""
    text = decode.as_text()
    lines = text.splitlines()
    entry = lines[next(i for i, l in enumerate(lines)
                       if l.startswith("ENTRY ")):]
    assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                          r'"tpu_custom_call"[^\n]*ff_mamba2_step',
                          text)) == mixers
    made = [m.group(1) for l in entry
            if (m := re.match(r"\s*%?[\w.\-]+ = f32\[16,128,64,128\]\S* "
                              r"([\w\-]+)\(", l))]
    assert sorted(set(made)) == ["get-tuple-element", "parameter"], made
    assert made.count("parameter") == made.count("get-tuple-element") \
        == mixers


def test_granite_serving_programs_fit_one_chip(described_devices, mosaic,
                                               one_chip, monkeypatch):
    """`granite-4.0-h-small.serve-chat`'s two programs at the cell's own
    sizes (16 slots, width 1024, 9.93 GB of bf16 weights), through the
    normal entry points: the chip's compiler must hold the prefill wave
    beside the weights, the state and the cache (its first compile ran
    1 GB over the chip: a `[tokens, k, d]` f32 combine and every mixer's
    xBC kept live to the program's end), and the experts' rows kernel and
    the flash kernel must be what it lowers to. The decode step appends to the
    pools it was handed: no whole-pool copy, every pool aliased."""
    eng, g, params, state = _described_engine(
        "granite-4.0-h-small.serve-chat", described_devices, monkeypatch,
        one_chip)
    slots = eng.slots
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert 0.65e9 < held < 0.75e9
    decode = eng._decode_jit.lower(
        params, state,
        [_i32(one_chip, slots, 1), _i32(one_chip, slots, 1)]).compile()
    prefill = eng._prefill_first_tokens_jit.lower(
        params, [_i32(one_chip, slots, g.seq), _i32(one_chip, slots, g.seq)],
        _i32(one_chip, slots)).compile()
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    for program, beside in ((decode, 0), (prefill, held)):
        m = program.memory_analysis()
        assert 9.9e9 < m.argument_size_in_bytes
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes + beside)
        assert need < 0.9 * chip, (need, m)
    assert prefill.memory_analysis().temp_size_in_bytes < 2.5e9
    text = prefill.as_text()
    assert "ff_moe_rows" in text and "ragged-dot" not in text
    _assert_appends_in_place(decode, eng)
    _assert_mixers_step_in_place(decode, 9)


def test_gigachat_serving_programs_fit_one_chip(described_devices, mosaic,
                                                one_chip, monkeypatch):
    """`GigaChat3.1-702B-A36B.serve-chat`'s two programs at the cell's own
    sizes (16 slots, width 1024, 10.35 GB of bf16 weights, six latent pools
    of `[1281, 16, 640]`: 576 values a row in whole lanes), through the normal entry points: the chip's
    compiler must hold the prefill wave beside the weights and the cache,
    the 192-wide heads must go through the flash kernel and the experts
    through the rows kernel, and the decode step must append to the
    pools it was handed (no whole-pool copy, every pool aliased)
    and attend in the latent space: no `[slots, context, heads, 320]`
    decompression of the cache exists in it."""
    from flexflow_tpu.serving import kv_cache

    eng, g, params, state = _described_engine(
        "GigaChat3.1-702B-A36B.serve-chat", described_devices, monkeypatch,
        one_chip)
    slots = eng.slots
    spec = eng.kv_spec
    assert (spec.latent_dim, spec.heads, spec.layers) == (576, 0, 6)
    pool = eng.kv.state[eng.attn_layers[0]]["latent"]
    assert pool.shape == (slots * 80 + 1, 16, 640) and pool.dtype == jnp.bfloat16
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert held == pytest.approx(spec.total_bytes(), rel=1e-3)
    assert 0.155e9 < held < 0.16e9
    three = [_i32(one_chip, slots, 1)] * 3
    decode = eng._decode_jit.lower(params, state, three).compile()
    wave = [_i32(one_chip, slots, g.seq)] * 3
    prefill = eng._prefill_first_tokens_jit.lower(
        params, wave, _i32(one_chip, slots)).compile()
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    for program, beside in ((decode, 0), (prefill, held)):
        m = program.memory_analysis()
        assert 10.3e9 < m.argument_size_in_bytes
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes + beside)
        assert need < 0.95 * chip, (need, m)
    text = prefill.as_text()
    assert "ff_moe_rows" in text and "ragged-dot" not in text
    # each of the five expert layers sizes its row buffers at run time: a
    # real conditional inside the loop over blocks (one branch runs), whose
    # branches need no more room than the whole block's rows do: that form
    # (no ladder, one buffer of tokens x k rows) is compiled beside it here,
    # and the two differ by 1.6 MB (3 431.8 against 3 430.2 MB; 3 260.2
    # against 3 258.5 at PR 62's tree: the wave is fullest elsewhere)
    assert len(re.findall(r" conditional\(", text)) >= 5
    from flexflow_tpu.ops import moe_ops

    monkeypatch.setattr(moe_ops, "_row_capacities", lambda pairs: [pairs])
    eng_w, _g, params_w, _state = _described_engine(
        "GigaChat3.1-702B-A36B.serve-chat", described_devices, monkeypatch,
        one_chip)
    whole = eng_w._prefill_first_tokens_jit.lower(
        params_w, wave, _i32(one_chip, slots)).compile()
    assert " conditional(" not in whole.as_text()
    m = prefill.memory_analysis()
    assert m.temp_size_in_bytes \
        <= whole.memory_analysis().temp_size_in_bytes + 4e6
    # `temp_size_in_bytes` is the compiler's heap: what is live where the
    # wave is fullest AND the holes between, of a schedule nothing pressed
    # (3.26 GB at PR 62's tree, 3.43 here; told that the chip has 12 GiB,
    # the same compiler packs PR 62's wave into 2.77 GiB and this one into
    # 2.72). What is live there is the number a buffer that outlives its
    # call would move: 2.670 GB, at layer 0's dense `[16,1024,36864]` and
    # its copy. Until PR 63 it read 2.963 GB, at a latent layer's flash
    # call: q, k, v, o of 512 MiB each (192 lanes in 256) and 512 MiB of
    # `lse` as `f32[16,64,1024,1]`, which a wave no longer writes
    live = prefill.runtime_executable().get_compiled_memory_stats() \
        .peak_memory_in_bytes - m.argument_size_in_bytes \
        - m.output_size_in_bytes
    assert live <= 2.68e9, live
    text = decode.as_text()
    assert " conditional(" not in text
    # the decompressed K/V of a slot's context: [.., 1280, 64, 320] or merged
    assert not re.search(r"\[16,1280,(64,320|20480|64,128|64,192|8192|12288)\]",
                         text)
    # six pools of 26 MB: the compiler stages some of them (4 here) through
    # its alternate memory, as it does granite's two: 52 MB read and written
    # back a staged pool and step, beside the 4 GB the step streams
    _assert_appends_in_place(decode, eng, staged_at_most=6)
    fresh = {n: {"latent": jax.ShapeDtypeStruct(
                     (slots, g.seq, 576), jnp.bfloat16, sharding=one_chip)}
             for n in eng.attn_layers}
    commit = kv_cache._commit_prefill.lower(
        state, fresh, _i32(one_chip, slots), _i32(one_chip, slots)).compile()
    _assert_appends_in_place(commit, eng, staged_at_most=6)


def test_nemotron_serving_programs_fit_one_chip(described_devices, mosaic,
                                                one_chip, monkeypatch):
    """`NVIDIA-Nemotron-3-Super-120B-A12B-BF16.serve-chat`'s two programs at
    the cell's own sizes (16 slots, width 1024, 9.30 GB of bf16 weights;
    of 11 layers 5 keep a recurrent state of 4.26 MB a slot, 1 pages K/V
    256 wide and 5 keep nothing), through the normal entry points: the
    chip's compiler must hold the prefill wave beside the weights, the state
    and the cache, the 8-group scans and the latent-wide rows kernel must
    be what it lowers to, and the decode step (352 pairs an expert
    layer: the first to get a ladder of row rungs) appends to the pools it
    was handed."""
    eng, g, params, state = _described_engine(
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.serve-chat", described_devices,
        monkeypatch, one_chip)
    slots = eng.slots
    spec = eng.kv_spec
    assert (spec.layers, spec.heads, spec.head_dim) == (1, 2, 128)
    assert spec.state_bytes_per_slot == 5 * (128 * 64 * 128 * 4
                                             + 3 * 10240 * 2)
    pool = eng.kv.state[eng.attn_layers[0]]["k"]
    assert pool.shape == (slots * 80 + 1, 16, 256)
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert 0.35e9 < held < 0.37e9
    two = [_i32(one_chip, slots, 1)] * 2
    decode = eng._decode_jit.lower(params, state, two).compile()
    wave = [_i32(one_chip, slots, g.seq)] * 2
    prefill = eng._prefill_first_tokens_jit.lower(
        params, wave, _i32(one_chip, slots)).compile()
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    for program, beside in ((decode, 0), (prefill, held)):
        m = program.memory_analysis()
        assert 9.29e9 < m.argument_size_in_bytes
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes + beside)
        assert need < 0.9 * chip, (need, m)
    # the wave's temporaries: 1.97 GB when this was written
    assert prefill.memory_analysis().temp_size_in_bytes < 2.5e9
    assert decode.memory_analysis().temp_size_in_bytes < 0.1e9
    text = prefill.as_text()
    assert "ff_moe_rows" in text and "ragged-dot" not in text
    assert len(re.findall(r" conditional\(", text)) >= 5
    _assert_appends_in_place(decode, eng)
    _assert_mixers_step_in_place(decode, 5)


def test_ling_serving_programs_fit_one_chip(described_devices, mosaic,
                                            one_chip, monkeypatch):
    """`Ling-3.0-flash.serve-chat`'s two programs at the cell's own sizes
    (16 slots, width 1024, 10.47 GB of bf16 weights; of 7 layers 6 keep a
    `[32, 128, 128]` f32 matrix state and a convolution tail a slot, 12.6 MB
    a slot, and 1 pages latents `[1281, 16, 640]`), through the normal entry
    points: the chip's compiler must hold the prefill wave beside the
    weights, the state and the cache, the six chunked delta-rule scans must
    compile (the Mosaic kernel `ff_kda_chunk_scan`, once a layer, under the
    scope of the same name), and the decode step appends to the one latent
    pool it was handed."""
    from flexflow_tpu import telemetry as tel

    eng, g, params, state = _described_engine(
        "Ling-3.0-flash.serve-chat", described_devices, monkeypatch, one_chip)
    slots = eng.slots
    spec = eng.kv_spec
    assert eng.kv.state_kinds == "paged_latent+recurrent"
    assert (spec.latent_dim, spec.heads, spec.layers) == (576, 0, 1)
    assert spec.state_bytes_per_slot == 6 * (32 * 128 * 128 * 4
                                             + 3 * 12288 * 2)
    pool = eng.kv.state[eng.attn_layers[0]]["latent"]
    assert pool.shape == (slots * 80 + 1, 16, 640)
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert 0.22e9 < held < 0.24e9
    three = [_i32(one_chip, slots, 1)] * 3
    decode = eng._decode_jit.lower(params, state, three).compile()
    wave = [_i32(one_chip, slots, g.seq)] * 3
    tel.ring_clear()
    prefill = eng._prefill_first_tokens_jit.lower(
        params, wave, _i32(one_chip, slots)).compile()
    # every KDA layer of the wave said which form its scan took
    assert [(s.args["path"], s.args["tile"], s.args["head_block"])
            for s in tel.ring_spans("kda/scan_path")] == [("kernel", 128, 8)] * 6
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    for program, beside in ((decode, 0), (prefill, held)):
        m = program.memory_analysis()
        assert 10.4e9 < m.argument_size_in_bytes
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes + beside)
        assert need < 0.95 * chip, (need, m)
    # the wave's temporaries: 2.89 GB since PR 42 (3.37 when the scans
    # were plain XLA, 2048 tokens at a time), the step's 24 MB
    assert prefill.memory_analysis().temp_size_in_bytes < 3.6e9
    assert decode.memory_analysis().temp_size_in_bytes < 0.1e9
    text = prefill.as_text()
    assert "ff_moe_rows" in text and "ragged-dot" not in text
    assert "ff_kda_chunk_scan" in text
    assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                          r'"tpu_custom_call"[^\n]*ff_kda_chunk_scan',
                          text)) == 6
    assert len(re.findall(r" conditional\(", text)) >= 6
    _assert_appends_in_place(decode, eng)


def test_brumby_serving_programs_fit_one_chip(described_devices, mosaic,
                                              one_chip, monkeypatch):
    """`Brumby-14B-Base.serve-longanswer`'s two programs at the cell's own
    sizes (16 slots, width 1024, 7.08 GB of bf16 weights; every one of the 6
    layers keeps an `[8, 8704, 128]` f32 state (the 8256 rows the
    recurrence needs, laid in whole tiles) and its `[8, 128, 128]`
    normaliser a slot, 36.18 MB a layer for 34.08 of need, 3.47 GB in all;
    nothing pages),
    through the normal entry points: no pools and no page accounting; the
    prefill wave is handed the slot arrays donated and writes them itself,
    so that the chip holds arguments + temporaries (no second `[16, 6, ...]`
    copy of the state among them: under two layers' worth), and the decode
    step updates the state it was handed in place: one Mosaic call a layer
    under `ff_power_retention_step`, the slot arrays aliased through it."""
    from flexflow_tpu import telemetry as tel

    eng, g, params, state = _described_engine(
        "Brumby-14B-Base.serve-longanswer", described_devices, monkeypatch,
        one_chip)
    slots = eng.slots
    spec = eng.kv_spec
    assert eng.kv.state_kinds == "recurrent" and eng.attn_layers == []
    assert (spec.layers, spec.heads, spec.latent_dim) == (0, 0, 0)
    layer_state = 8 * (8704 * 128 + 128 * 128) * 4
    assert spec.state_bytes_per_slot == 6 * layer_state == 6 * 36175872
    assert g.state_bytes_per_slot() == 6 * 34080768     # the need
    assert eng.kv.writes_state_in_place
    assert eng.kv.pages_needed(1024) == 0 and eng.kv.can_admit(10 ** 6)
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert 3.47e9 < held < 3.48e9
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert weights == 2 * 3537947136
    three = [_i32(one_chip, slots, 1)] * 3
    tel.ring_clear()
    decode = eng._decode_jit.lower(params, state, three).compile()
    wave = [_i32(one_chip, slots, g.seq)] * 3
    slot_state = {n: state[n] for n in eng.kv.recurrent}
    prefill = eng._prefill_first_tokens_jit.lower(
        params, wave, _i32(one_chip, slots), slot_state).compile()
    # every layer of the wave said which regime its sequence took
    assert [(s.args["path"], s.args["chunk"])
            for s in tel.ring_spans("retention/path")] == [("pair", 1024)] * 6
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    for program in (decode, prefill):
        m = program.memory_analysis()
        # the weights and the whole state are arguments, the state aliased
        # to the outputs: arguments + temporaries is what the chip holds
        assert 10.5e9 < m.argument_size_in_bytes < 10.6e9
        assert m.alias_size_in_bytes > 3.47e9
        need = m.argument_size_in_bytes + m.temp_size_in_bytes \
            + m.output_size_in_bytes - m.alias_size_in_bytes
        assert need < 15e9 < chip, (need, m)
    # the wave's temporaries hold no second copy of the state (3.47 GB;
    # the MLPs' two `[16, 1024, 34816]` intermediates are 2.3 of the 3.24)
    assert prefill.memory_analysis().temp_size_in_bytes < 3.6e9
    assert decode.memory_analysis().temp_size_in_bytes < 0.2e9
    for program in (decode, prefill):
        # no whole-state copy in either entry computation
        big = [(op, t) for op, t in _entry_ops(program.as_text())
               if op in ("copy", "copy-start", "transpose")
               and "f32[16,8,8704,128]" in t]
        assert not big, big
    # the step: the kernel, once a layer, and every layer said so
    assert [(s.args["path"], s.args["laid_rows"])
            for s in tel.ring_spans("retention/step_path")] \
        == [("kernel", 8704)] * 6
    assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                          r'"tpu_custom_call"[^\n]*ff_power_retention_step',
                          decode.as_text())) == 6


def _entry_ops(text):
    """(op name, result type) of every instruction of optimized HLO's
    entry computation."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY "))
    ops = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if m:
            ops.append((m.group(2), m.group(1)))
    return ops


def _assert_appends_in_place(program, eng, staged_at_most=2):
    """No op of the program's entry computation copies or relays an array
    of a pool's size (`copy`, `copy-start`, `reshape`, `transpose`: the
    pools themselves, and the gathered context, which is as large), and
    the compiler aliases every pool leaf it was told is donated onto an
    output. One thing is let through: where the pools are few (granite's
    two) the compiler stages them through its alternate memory (`S(1)`;
    four slices in, the scatter and the gather there, one `copy-start` back
    onto the aliased buffer) — its own placement, as on the parent, no
    relayout and no fresh buffer."""
    pools = [leaf for n in eng.attn_layers
             for leaf in eng.kv.state[n].values()]
    size = pools[0].size         # slots * pages_per_slot + 1 pages: both
    moved, staged = [], 0
    for op, ty in _entry_ops(program.as_text()):
        if op not in ("copy", "copy-start", "reshape", "transpose") \
                or not 0.9 * size <= (_elements(ty) or 0) <= size:
            continue
        layouts = re.findall(r"\{[^}]*\}", ty)     # copy-start: dest, source
        if op == "copy-start" and "S(1)" in layouts[1] \
                and "S(1)" not in layouts[0]:
            staged += 1
        else:
            moved.append((op, ty))
    assert not moved, (len(moved), moved[:4])
    assert staged <= staged_at_most, staged
    held = sum(leaf.size * leaf.dtype.itemsize for n in eng.attn_layers
               for leaf in eng.kv.state[n].values())
    assert program.memory_analysis().alias_size_in_bytes >= held


def test_lfm2_moe_serving_programs_fit_one_chip(described_devices, mosaic,
                                                one_chip, monkeypatch):
    """`LFM2-24B-A2B.serve-longanswer`'s two programs at the cell's own sizes
    (16 slots, width 1024, 10.62 GB of bf16 weights: every one of the 64
    experts of all eight expert layers; of 9 layers 2 page K/V 512 wide and
    7 keep a convolution state of 8 KB a slot), through the normal entry
    points: the chip's compiler must hold the prefill wave and the decode
    step beside the weights and the cache (arguments + temporaries under 15
    GB), attention must go through the flash kernel and the wave's experts
    through the rows kernel, the wave's expert layers must carry the
    ladder's conditional (a whole-holder with `valid`), the decode step none
    (its experts are the step kernel: the test after this one), and the
    decode step appends to the pools it was handed."""
    eng, g, params, state = _described_engine(
        "LFM2-24B-A2B.serve-longanswer", described_devices, monkeypatch,
        one_chip)
    slots = eng.slots
    spec = eng.kv_spec
    assert (spec.layers, spec.heads, spec.head_dim) == (2, 8, 64)
    assert spec.state_bytes_per_slot == 7 * 2 * 2048 * 2
    assert len(eng.attn_layers) == 2 and len(eng.kv.recurrent) == 7
    assert eng.kv.state_kinds == "paged_kv+recurrent"
    moe = [l for l in eng.decode_model.layers if l.op_type.value == "moe_layer"]
    assert len(moe) == 8
    assert all(l.params["experts_held"] == (0, 64) for l in moe)
    pool = eng.kv.state[eng.attn_layers[0]]["k"]
    assert pool.shape == (slots * 96 + 1, 16, 512)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert weights == 2 * 5312168704 + 2 * 8 * 64    # the f32 selection biases
    three = [_i32(one_chip, slots, 1)] * 3
    decode = eng._decode_jit.lower(params, state, three).compile()
    wave = [_i32(one_chip, slots, g.seq)] * 3
    prefill = eng._prefill_first_tokens_jit.lower(
        params, wave, _i32(one_chip, slots)).compile()
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert 0.10e9 < held < 0.11e9
    for program, beside in ((decode, 0), (prefill, held)):
        m = program.memory_analysis()
        assert 10.6e9 < m.argument_size_in_bytes
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes + beside)
        assert need < 15e9 < chip, (need, m)
    text = prefill.as_text()
    assert "ff_moe_rows" in text and "ragged-dot" not in text
    assert " conditional(" in text
    assert " conditional(" not in decode.as_text()
    # the step's experts are the kernel, once a layer, under the scope that
    # `moe_experts_roofline.decode.lfm2` reads
    from flexflow_tpu import attribution
    from flexflow_tpu.ops.moe_ops import EXPERTS_SCOPE
    under = attribution.instructions_in_scope(decode.as_text(), EXPERTS_SCOPE)
    assert sum(n.startswith("ff_moe_step") for n in under) == 8
    # two layers' K and V pools: the compiler stages all four (as GigaChat's)
    _assert_appends_in_place(decode, eng, staged_at_most=4)


def test_keye_vl_serving_programs_fit_one_chip(described_devices, mosaic,
                                               one_chip, monkeypatch):
    """`Keye-VL-2.0-30B-A3B.serve-longprompt`'s two programs at the cell's
    own sizes (16 slots of 16896 positions, 8.75 GB of bf16 weights: every
    one of the 128 experts of all six layers; six layers page K/V 512 wide
    and six indexers their key beside it), through the normal entry points:
    the prompt program is the `[1, 2048]` chunk over the slots' own pages
    (the `[16, 16896]` wave is never compiled), and the chip's compiler must
    hold the chunk and the decode step beside the weights and the cache
    (arguments + temporaries under 15.5 GB). The decode step's experts are
    the step kernel at the width of 768, its indexer finds the 2048th largest
    score bit by bit (`keep_mask`: no sort, no `lax.top_k`) and hands the
    membership mask on, and its attention is the kernel `ff_sparse_attend_step`
    a layer, which fetches the live slots' pages from the pools where they
    lie: no value of a slot's kept rows or of a whole slot context's K or V
    exists in it, and no pool is copied. The chunk's attention is the kernel
    `ff_sparse_attend_chunk` a layer over the slot's gathered pages: no
    float32 scores of 8 x 256 rows against a rung's keys exist in it. Both
    programs append to the pools they were handed."""
    eng, g, params, state = _described_engine(
        "Keye-VL-2.0-30B-A3B.serve-longprompt", described_devices,
        monkeypatch, one_chip)
    slots, spec = eng.slots, eng.kv_spec
    assert (spec.layers, spec.heads, spec.head_dim) == (6, 4, 128)
    assert (spec.index_layers, spec.index_dim) == (6, 64)
    assert spec.pages_per_slot * spec.page_size == g.seq == 16896
    assert len(eng.attn_layers) == 12 and len(eng.kv.index_layers) == 6
    assert eng.kv.state_kinds == "paged_kv+paged_index"
    assert eng.chunk_tokens == 2048
    pages = slots * 1056 + 1
    assert eng.kv.state["l0_attn"]["k"].shape == (pages, 16, 512)
    assert eng.kv.state["l0_index"]["ik"].shape == (pages, 16, 128)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert weights == 2 * 4374622464
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert 3.7e9 < held < 3.8e9
    decode = eng._decode_jit.lower(
        params, state, [_i32(one_chip, slots, 1), _i32(one_chip, slots, 1, 3),
                        _i32(one_chip, slots, 1)]).compile()
    chunk = eng._chunk_jit.lower(
        params, state, [_i32(one_chip, 1, 2048), _i32(one_chip, 1, 2048, 3),
                        _i32(one_chip, 1, 2048)],
        _i32(one_chip, 1, 1056), _i32(one_chip, 1), _i32(one_chip, 1)
    ).compile()
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    for program in (decode, chunk):
        m = program.memory_analysis()
        assert 12.4e9 < m.argument_size_in_bytes
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert need < 15.5e9 < chip, (need, m)
        assert m.alias_size_in_bytes >= held - 1e6
    text = decode.as_text()
    from flexflow_tpu import attribution
    from flexflow_tpu.ops.moe_ops import EXPERTS_SCOPE
    from flexflow_tpu.ops.sparse_attention_ops import (ATTEND_SCOPE,
                                                       INDEX_SCOPE)
    under = attribution.instructions_in_scope(text, EXPERTS_SCOPE)
    assert sum(n.startswith("ff_moe_step") for n in under) == 6
    assert "ragged-dot" not in text
    # a chunk's experts are the rows kernel, once a rung and layer
    _assert_experts_are_the_rows_kernel(chunk.as_text(), 3 * 6)
    assert attribution.instructions_in_scope(text, INDEX_SCOPE)
    under = attribution.instructions_in_scope(text, ATTEND_SCOPE)
    assert sum(n.startswith("ff_sparse_attend_step") for n in under) == 6
    assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                          r'"tpu_custom_call"[^\n]*ff_sparse_attend_step',
                          text)) == 6
    # a step holds neither a slot's kept rows of K or V nor its context's
    assert f"bf16[{slots},16896,512]" not in text
    assert f"bf16[{slots},2048,512]" not in text
    # the pools go into the kernel where they lie: nothing copies one
    pool = f"bf16[{pages},16,512]"
    assert not re.search(rf"= {re.escape(pool)}\S* copy\(", text)
    # a chunk's attention under the mask is the kernel, once a layer: its
    # scores never leave VMEM (no float32 value of a query block's heads
    # against a rung's keys), the rungs' `lax.switch` is the indexer's alone,
    # and the pools go in by the page gather: nothing copies one
    text = chunk.as_text()
    assert attribution.instructions_in_scope(text, INDEX_SCOPE)
    under = attribution.instructions_in_scope(text, ATTEND_SCOPE)
    assert sum(n.startswith("ff_sparse_attend_chunk") for n in under) == 6
    assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                          r'"tpu_custom_call"[^\n]*ff_sparse_attend_chunk',
                          text)) == 6
    for keys in (4224, 8448, 12672, 16896):
        assert not re.search(rf"f32\[[0-9,]*,2048,{keys}\]", text)
    assert not re.search(rf"= {re.escape(pool)}\S* copy\(", text)


def test_mellum_serving_programs_fit_one_chip(described_devices, mosaic,
                                              one_chip, monkeypatch):
    """`Mellum2-12B-A2.5B-Instruct.serve-longprompt`'s two programs at the
    cell's own sizes (16 slots of 16896 positions, 10.93 GB of bf16 weights:
    every one of the 64 experts of all twelve layers; three full layers page
    1056 pages a slot and nine windowed ones a ring of 193), through the
    normal entry points: the prompt program is the `[1, 2048]` chunk over the
    slot's own pages and ring, and the chip's compiler must hold the chunk
    and the decode step beside the weights and both extents of the cache
    (arguments + temporaries under 15 GB: ISSUE 56's rule for twelve layers).
    Mosaic accepts the two attention kernels with bounds by position at
    these tiles, one call a layer in each program (the step kernel fetches
    the pools' pages where they lie; the chunk kernel reads the gathered
    pages), and the experts' step kernel at a width of 896 = 7 x 128. No
    mask `[b, s, L]` and no float32 scores of a chunk's queries against a
    slot's context exist in either program; both append to the pools they
    were handed."""
    eng, g, params, state = _described_engine(
        "Mellum2-12B-A2.5B-Instruct.serve-longprompt", described_devices,
        monkeypatch, one_chip)
    slots, spec = eng.slots, eng.kv_spec
    layers = g.layers
    full, ringed = layers // 4, 3 * layers // 4
    assert (spec.layers, spec.window_layers, spec.heads, spec.head_dim) \
        == (full, ringed, 4, 128)
    assert spec.pages_per_slot * spec.page_size == g.seq == 16896
    assert spec.window_pages == 193
    assert eng.kv.state_kinds == "paged_kv+paged_kv_ring"
    assert eng.chunk_tokens == 2048
    pages, ring_pages = slots * 1056 + 1, slots * 193 + 1
    assert eng.kv.state["l3_attn"]["k"].shape == (pages, 16, 512)
    assert eng.kv.state["l0_attn"]["k"].shape == (ring_pages, 16, 512)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert weights == 2 * g.param_count()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert held - 1e6 < spec.total_bytes() < held
    if layers == 12:
        assert weights == 2 * 5465959680 and 2.57e9 < held < 2.59e9
    decode = eng._decode_jit.lower(
        params, state, [_i32(one_chip, slots, 1)] * 3).compile()
    chunk = eng._chunk_jit.lower(
        params, state, [_i32(one_chip, 1, 2048)] * 3,
        _i32(one_chip, 1, 1056 + 193), _i32(one_chip, 1), _i32(one_chip, 1)
    ).compile()
    chip = 15.75e9          # what the compiler has of a v5e chip's 16 GB
    needs = {}
    for name, program in (("decode", decode), ("chunk", chunk)):
        m = program.memory_analysis()
        needs[name] = (m.argument_size_in_bytes + m.output_size_in_bytes
                       - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert needs[name] < 15e9 < chip, (name, needs[name], m)
        assert m.alias_size_in_bytes >= held - 1e6
    print("mellum serving programs need", needs)
    from flexflow_tpu import attribution
    from flexflow_tpu.ops.attention_ops import (BOUNDED_SCOPE, FULL_SCOPE,
                                                WINDOW_SCOPE)
    from flexflow_tpu.ops.moe_ops import EXPERTS_SCOPE
    for program, kernel in ((decode, "ff_sparse_attend_step"),
                            (chunk, "ff_sparse_attend_chunk")):
        text = program.as_text()
        for scope, n in ((WINDOW_SCOPE, ringed), (FULL_SCOPE, full),
                         (BOUNDED_SCOPE, layers)):
            under = attribution.instructions_in_scope(text, scope)
            assert sum(name.startswith(kernel) for name in under) == n, scope
        assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                              rf'"tpu_custom_call"[^\n]*{kernel}',
                              text)) == layers
        # no membership mask over a slot's context, no scores outside VMEM
        for keys in (3088, 16896):
            assert not re.search(rf"pred\[[0-9,]*,{keys}\]", text)
            assert not re.search(rf"f32\[[0-9,]*,2048,{keys}\]", text)
        # the pools go in where they lie or by the page gather: no copy
        for pool in (f"bf16[{pages},16,512]", f"bf16[{ring_pages},16,512]"):
            assert not re.search(rf"= {re.escape(pool)}\S* copy\(", text)
    text = decode.as_text()
    under = attribution.instructions_in_scope(text, EXPERTS_SCOPE)
    assert sum(n.startswith("ff_moe_step") for n in under) == layers
    assert "ragged-dot" not in text
    # a chunk's experts are the rows kernel, once a rung and layer (1024,
    # 4096 and all 16 384 rows): no grouped product of XLA's is left in it
    _assert_experts_are_the_rows_kernel(chunk.as_text(), 3 * layers)
    # a step holds no slot's gathered context or window
    assert f"bf16[{slots},16896,512]" not in text
    assert f"bf16[{slots},3088,512]" not in text


def _assert_experts_are_the_rows_kernel(text, calls):
    """A compiled wave or chunk program's expert layers: `calls` Mosaic
    calls `ff_moe_rows` under `ff_moe_experts` (`kernels/moe_rows.py`, one a
    rung of a layer's `lax.switch`) and no grouped product of XLA's own."""
    from flexflow_tpu import attribution
    from flexflow_tpu.ops.moe_ops import EXPERTS_SCOPE

    under = attribution.instructions_in_scope(text, EXPERTS_SCOPE)
    assert sum(n.startswith("ff_moe_rows") for n in under) == calls
    assert len(re.findall(r' custom-call\([^\n]*custom_call_target='
                          r'"tpu_custom_call"[^\n]*ff_moe_rows',
                          text)) == calls
    assert "ragged-dot" not in text


MOE_CELLS = {   # cell: (inputs of its programs, expert layers, a tile's tn)
    "granite-4.0-h-small.serve-chat": (2, 10, 768),
    "GigaChat3.1-702B-A36B.serve-chat": (3, 5, 512),
    "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.serve-chat": (2, 5, 2688),
    "Ling-3.0-flash.serve-chat": (3, 6, 768),
    "LFM2-24B-A2B.serve-longanswer": (3, 8, 1536)}


@pytest.mark.parametrize("cell", list(MOE_CELLS))
def test_a_decode_steps_experts_are_the_step_kernel(cell, described_devices,
                                                    mosaic, one_chip,
                                                    monkeypatch):
    """Each expert family's decode program at its cell's sizes, through the
    chip's compiler: every expert layer's held experts are one Mosaic call
    `ff_moe_step` under `ff_moe_experts` (the tile as
    `moe_step.tile_width` sizes it for the served widths), no grouped
    product is left in the step, and none of the pair sort, the row gather
    and the combine's gathers under an expert layer's scope (the router's
    `top_k` is the one sort there). The prefill program, whose blocks are
    4096 tokens, lowers to the rows kernel at every rung (asked of its
    StableHLO: the family's own test compiles it)."""
    from flexflow_tpu import attribution
    from flexflow_tpu.kernels import moe_step
    from flexflow_tpu.ops import moe_ops

    inputs, layers, tn = MOE_CELLS[cell]
    eng, g, params, state = _described_engine(cell, described_devices,
                                              monkeypatch, one_chip)
    slots = eng.slots
    moe = [l for l in eng.decode_model.layers
           if l.op_type.value == "moe_layer"]
    assert len(moe) == layers
    p = moe[0].params
    assert moe_ops._step_tile(slots, moe[0].inputs[0].spec.shape[-1], 2,
                              p) == tn
    text = eng._decode_jit.lower(
        params, state, [_i32(one_chip, slots, 1)] * inputs).compile().as_text()
    calls = re.findall(r' custom-call\([^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*ff_moe_step', text)
    assert len(calls) == layers
    assert "ragged-dot" not in text
    under = attribution.instructions_in_scope(text, moe_ops.EXPERTS_SCOPE)
    assert sum(n.startswith("ff_moe_step") for n in under) == layers
    # what sorts, gathers or scatters under an expert layer's own scope is
    # the router's choice (`_choose`: `top_k`, the take of the chosen scores)
    layer_scopes = "|".join(re.escape(l.name) for l in moe)
    for _op, what in re.findall(
            r' (sort|gather|scatter)\([^\n]*op_name="[^"\n]*/(?:%s)/([^"\n]*)"'
            % layer_scopes, text):
        assert what in ("ff_moe_router/top_k",
                        "ff_moe_router/jit(take_along_axis)/gather"), what
    wave = eng._prefill_first_tokens_jit.lower(
        params, [_i32(one_chip, slots, g.seq)] * inputs,
        _i32(one_chip, slots)).as_text()
    assert "ff_moe_rows" in wave and "ragged_dot" not in wave \
        and "ff_moe_step" not in wave


def test_gpt2_medium_decode_and_commit_append_in_place(described_devices,
                                                       one_chip, monkeypatch):
    """`gpt2-medium.serve-chat`'s decode step and prefill commit at the
    cell's geometry (16 slots, `max_decode_len` 256, page 16, bf16). With
    head_dim 64 in the minor dimension the chip's default layout of a
    `[pages, page, h, d]` pool puts the page index in the lanes, and every
    step relaid every pool for the scatter and back for the output: 2
    copies x 2 pools x 24 layers of 42 MB each, and nothing aliased. The
    pools are `[pages, page, h * d]` at rest, the state is donated and the
    gathered context is read as it lies (split into heads of 64 it was
    relaid again, 48 `reshape` a step), so the only whole-pool op left is
    the page gather."""
    from flexflow_tpu.serving import kv_cache

    eng, g, params, state = _described_engine(
        "gpt2-medium.serve-chat", described_devices, monkeypatch, one_chip)
    slots = eng.slots
    decode = eng._decode_jit.lower(
        params, state,
        [_i32(one_chip, slots, 1), _i32(one_chip, slots, 1)]).compile()
    _assert_appends_in_place(decode, eng)
    spec = eng.kv_spec
    fresh = {n: {key: jax.ShapeDtypeStruct(
                     (slots, g.seq, spec.heads, spec.head_dim),
                     jnp.bfloat16, sharding=one_chip) for key in ("k", "v")}
             for n in eng.attn_layers}
    commit = kv_cache._commit_prefill.lower(
        state, fresh, _i32(one_chip, slots), _i32(one_chip, slots)).compile()
    _assert_appends_in_place(commit, eng)


def test_trinity_train_step_fits_one_chip(described_devices, mosaic):
    """The cell Trinity-Mini.train-8k's training step through the normal
    entry points, compiled for one described chip from shapes: arguments +
    temporaries under 15 GB (ISSUE 58's rung), every attention layer through
    the flash kernels (no [.., 8192, 8192] scores anywhere in the program),
    the held experts' forward through kernels/moe_rows.py, and the routing
    decision of an expert layer made ONCE a block (PR 59: the `top_k` and
    the sorts under the `moe_layer` scopes in the chip's compiled text, all
    phases over the forward's; 3 before: the `remat_blocks` unit's
    recomputation and the block's each decided again), and the flash
    forward kernel run ONCE a layer (PR 61: the unit keeps what it writes,
    so the five kept pairs cost 0.68 GB and not 2 GB; since PR 63 `lse`
    lane-dense as the kernel writes it), and the rows kernel's forward run
    TWICE a layer (PR 64: the unit keeps the layer's `y`, 67 MB a layer, so
    its recomputation multiplies no row; 3 before. The second run is the
    block's backward, whose gates' gradient reads the products' result)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from families import family_of
    from harness import manifest as mf

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.compiler.compile import build_state_init_fn
    from flexflow_tpu.core.graph import topo_order

    described_devices(1)
    cell = mf.load_cell(mf.load_manifest(), "Trinity-Mini.train-8k")
    batch = cell.traffic["global_batch"]
    model = FFModel(FFConfig(batch_size=batch, seed=1, strategy_cache=False,
                             log_level="warning", **cell.system["ffconfig"]))
    gcfg = family_of(cell.config).build(model, cell.config, batch)
    cm = model.compile(AdamOptimizer(alpha=cell.system["adam_lr"]),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    params, opt, _, ins, label, key = _train_step_shapes(cm, (batch, gcfg.seq))
    state = jax.eval_shape(
        build_state_init_fn(topo_order(model.layers),
                            model._initializer_overrides),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=key.sharding)
             for k, v in state.items()}
    assert len(state) == 4 and "l1_moe/score_bias" in state
    assert all("score_bias" not in leaves for leaves in params.values())
    compiled = cm.train_step.lower(params, opt, state, ins, label,
                                   key).compile()
    m = compiled.memory_analysis()
    held = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params))
    assert held + 4 * 128 == 705_474_304
    assert m.argument_size_in_bytes >= 12 * held
    # the room the next kept tensor is sized from (`CHANGES.md` quotes it)
    print("trinity step: arguments", m.argument_size_in_bytes, "+ temporaries",
          m.temp_size_in_bytes, "=",
          m.argument_size_in_bytes + m.temp_size_in_bytes, "room left",
          int(15e9) - m.argument_size_in_bytes - m.temp_size_in_bytes)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15e9, m
    text = compiled.as_text()
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    assert "ff_moe_rows" in text
    # five attention layers: each kernel once a layer, the forward one too
    # (PR 61: the unit keeps its `o` and `lse`; ten calls before)
    for kernel in ("ff_flash_attention_fwd", "ff_flash_attention_dq",
                   "ff_flash_attention_dkv"):
        calls = re.findall(rf"%{kernel}[.\d]* = \S.* custom-call\(", text)
        assert len(calls) == 5, (kernel, len(calls))
    from flexflow_tpu import attribution

    op_types = {l.name: l.op_type.value for l in model.layers}
    # PR 63: the kept `lse` is `f32[2,32,1,8192]` as it lies (2.1 MB a
    # layer), no statistic is `[.., 8192, 1]`, and no q-sized `copy` /
    # `transpose` stands under an attention layer (9 a layer before): the
    # kernels read `[b, s, h * d]` as the projections and the head norm and
    # rotation (`ff_head_turn_*`, on the merged axis too) write it
    assert attribution.step_passes(text, op_types) \
        == {"moe_routing_passes": 1.0, "flash_fwd_passes": 1.0,
            "moe_rows_passes": 2.0, "flash_relayouts": 0.0}
    # four expert layers, three rungs that multiply rows, two passes
    calls = re.findall(r"%ff_moe_rows[.\d]* = \S.* custom-call\(", text)
    assert len(calls) == 4 * 3 * 2, len(calls)
    assert not re.search(r"f32\[\d+,\d+,8192,1\]", text)
    assert "f32[2,32,1,8192]{3,2,1,0:T(1,128)" in text
    for kernel, calls in (("ff_head_turn_fwd", 20), ("ff_head_turn_bwd", 10)):
        found = re.findall(rf"%{kernel}[.\d]* = \S.* custom-call\(", text)
        assert len(found) == calls, (kernel, len(found))


def test_jamba_serving_programs_fit_one_chip(described_devices, mosaic,
                                             one_chip, monkeypatch):
    """`AI21-Jamba2-3B.serve-longprompt`'s two programs at the cell's own
    sizes (16 slots of 16896 positions, 6.39 GB of bf16 weights: all 28
    layers, the whole vocabulary; two attention layers page 1056 pages a slot
    of one K/V head, 26 Mamba layers keep a `[16, 5120]` float32 state and a
    conv tail a slot), through the normal entry points: the prompt program is
    the `[1, 2048]` chunk that starts every Mamba layer from its slot's
    state. Mosaic accepts the selective-scan kernel at these tiles, one call
    a Mamba layer, and the two attention kernels by position at 20 query
    heads over ONE K/V head; no float32 `[2048, 16, 5120]` intermediate of
    the scan and no scores of a chunk's queries against a slot's context
    exist in the chunk program; both programs write the state and the pools
    they were handed in place."""
    eng, g, params, state = _described_engine(
        "AI21-Jamba2-3B.serve-longprompt", described_devices, monkeypatch,
        one_chip)
    slots, spec = eng.slots, eng.kv_spec
    assert (g.layers, g.layer_types.count("attention")) == (28, 2)
    assert (spec.layers, spec.heads, spec.head_dim) == (2, 1, 128)
    assert spec.pages_per_slot * spec.page_size == g.seq == 16896
    assert eng.kv.state_kinds == "paged_kv+recurrent"
    assert eng.chunk_tokens == 2048 and len(eng.kv.recurrent) == 26
    pages = slots * 1056 + 1
    assert eng.kv.state["l7_attn"]["k"].shape == (pages, 16, 128)
    assert eng.kv.state["l0_mamba"]["ssm"].shape == (slots, 16, 5120)
    assert eng.kv.state["l0_mamba"]["conv"].shape == (slots, 3, 5120)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    # A_log, D and dt_bias are float32: 2 more bytes each
    assert weights == 2 * g.param_count() + 26 * 2 * (16 + 2) * 5120
    assert g.param_count() == 3197109632
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
    assert spec.state_bytes_per_slot == g.state_bytes_per_slot() == 9318400
    assert 0.42e9 < held < 0.43e9
    decode = eng._decode_jit.lower(
        params, state, [_i32(one_chip, slots, 1)] * 2).compile()
    chunk = eng._chunk_jit.lower(
        params, state, [_i32(one_chip, 1, 2048)] * 2,
        _i32(one_chip, 1, 1056), _i32(one_chip, 1), _i32(one_chip, 1),
        _i32(one_chip, 1)).compile()
    needs = {}
    for name, program in (("decode", decode), ("chunk", chunk)):
        m = program.memory_analysis()
        needs[name] = (m.argument_size_in_bytes + m.output_size_in_bytes
                       - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert 6.8e9 < needs[name] < 9e9, (name, needs[name], m)
        assert m.alias_size_in_bytes >= held - 1e6
    print("jamba serving programs need", needs)
    from flexflow_tpu import attribution
    from flexflow_tpu.ops.attention_ops import FULL_SCOPE
    from flexflow_tpu.ops.mamba_ops import SCAN_SCOPE, STEP_SCOPE

    def kernel_calls(text, kernel):
        return len(re.findall(r' custom-call\([^\n]*custom_call_target='
                              rf'"tpu_custom_call"[^\n]*{kernel}', text))

    text = chunk.as_text()
    assert kernel_calls(text, "ff_selective_scan") == 26
    assert kernel_calls(text, "ff_sparse_attend_chunk") == 2
    under = attribution.instructions_in_scope(text, SCAN_SCOPE)
    assert sum(n.startswith("ff_selective_scan") for n in under) == 26
    under = attribution.instructions_in_scope(text, FULL_SCOPE)
    assert sum(n.startswith("ff_sparse_attend_chunk") for n in under) == 2
    assert not re.search(r"f32\[[0-9,]*2048,16,5120\]", text)
    assert not re.search(r"f32\[[0-9,]*,2048,16896\]", text)
    text = decode.as_text()
    assert kernel_calls(text, "ff_sparse_attend_step") == 2
    assert attribution.instructions_in_scope(text, STEP_SCOPE)
    for program in (chunk, decode):
        for pool in (f"bf16[{pages},16,128]", "f32[16,16,5120]"):
            assert not re.search(rf"= {re.escape(pool)}\S* copy\(",
                                 program.as_text())
