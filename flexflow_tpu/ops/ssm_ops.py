"""State-space mixer: Mamba-2 (Dao & Gu 2024, "Transformers are SSMs"), in
the layout of Hugging Face's Mamba2 / GraniteMoeHybrid mixers.

    [z | xBC | dt] = x W_in                 sizes d_inner | d_inner + 2 G N | H
    xBC = silu(causal depthwise conv1d(xBC, width d_conv) + b_conv)
    [u | B | C] = xBC                       u: [H heads, P = d_inner / H]
                                            B, C: [G groups, N] each
    dt = softplus(dt + dt_bias);  A = -exp(A_log)        one scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t u_t (x) B_t         S: [H, P, N], float32
    y_t = S_t C_t + D u_t                   head h reads B, C of group h // (H / G)
    out = RMS_G(y * silu(z); w_norm) W_out

`n_groups` G (1 unless the layer's params say otherwise): the heads lie in G
groups of H / G consecutive heads that share one B and one C, and the gated
norm RMS_G normalises each group's d_inner / G values apart (one weight of
d_inner); with G = 1 that is one B, one C and a norm over all d_inner. A
decode step with G = 1 lowers to the program it lowered to before groups
existed.

Three forms of one op, chosen by `params["mode"]`:

- None (training, evaluation): the whole sequence by chunks (the SSD form:
  inside a chunk the recurrence is one masked matrix product a head,
  between chunks the state is carried). Equal to the recurrence at any
  length and at any chunk; a length that is no multiple of the chunk is
  padded with steps that change nothing. `params["chunk"]` is the
  configuration's published chunk size: it bounds the tile the scan takes
  and is not mathematics.
- "state_out" (serving prefill): the same, and the state is handed out in
  `ctx.new_state[layer.name] = {"ssm": [b, H, P, N] f32, "conv": [b,
  d_conv - 1, conv_dim]}`.
- "decode" (serving decode): one step of the recurrence on
  `ctx.state[layer.name]`, written back to `ctx.new_state`. Reports
  (ctx.add_stat) `ssm_state_bytes`: the state the step's live slots read
  and wrote (both leaves, twice), and `ssm_step_kernel_slots`: the slots
  the step kernel took (below; 0 where the step took the XLA form).

The sequence forms are one algorithm at every size, its form chosen from the
shapes alone (`scan_path`, reported a lowered layer by the `ssm/scan_path`
span):

- the kernel (`kernels/ssd_scan.py`, `ff_ssd_chunk_scan`) where a group's
  heads and the state fill whole lanes (the served widths: heads of 64, a
  state of 128): the `[chunk, chunk]` decay mask, `C B^T`, their product
  with `dt` and the product with `u` live on a tile in VMEM, the state is
  carried in VMEM over the tiles of a row, and the skip, the gate and the
  group's norm are applied to the tile before it is written, so of the scan
  only its result in the compute type and the last state reach HBM. Its
  gradient is the XLA form's (`custom_vjp`, recomputed).
- the XLA form (`_ssd_xla`, jax.numpy; gradients from JAX) elsewhere: the
  same products batched over the chunks with the heads leading, the
  `[rows, heads, chunk, chunk]` intermediates streamed through HBM for
  `MAMBA_TOKEN_BLOCK` tokens at a time.

The second input, `valid` `[b, s]` (int, 1 = a real token), says which
positions exist: at the others `dt` is 0 (the state neither decays nor
takes anything in) and nothing enters the conv tail. A right-padded prompt
wave therefore hands out each row's state after its LAST REAL token, and a
decode step advances only the slots that `valid` names. Without the input
every position is real.

The decode step's recurrence (`ssm_step`: the update of the state and the
read-out `S C` of the new one) has two forms too, chosen from the shapes
alone (`step_path`, reported a lowered layer by the `ssm/step_path` span):

- the kernel (`kernels/mamba2_step.py`, `ff_mamba2_step`) where a head's
  `[P, N]` f32 state is whole tiles (N whole 128-lane slabs, P whole sublane
  tiles: the served widths), a B/C group's heads are whole sublane tiles (a
  turn of the kernel's loop takes eight heads with one B and one C) and the
  program runs on one device (GSPMD cannot partition a Mosaic call): a
  grid over (live slot, block of heads) whose first bound is the live count,
  a live slot's state read from HBM once and written once, in place in the
  donated slot array, the read-out made from the tile while it is in VMEM.
  A slot that is not live costs no grid step, keeps its bytes, and reads
  y = 0.
- the XLA form (`_step_xla`, jax.numpy) elsewhere (every tiny model, d_state
  16): one pass over ALL the slots' state, a slot that is not live
  multiplied by 1 (`dt` = 0) and written back.

The projections, the conv window and its SiLU, `dt`'s softplus, the skip,
the gate and the norm are plain XLA (jax.numpy) in every form of the step.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels import mamba2_step as step_kernel
from flexflow_tpu.kernels.partition import multi_device
from flexflow_tpu.kernels.ssd_scan import (scan_tiles, ssd_chunk_scan,
                                           ssd_chunk_scan_gated)
from flexflow_tpu.ops.norm_ops import rms_norm
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx, register_op


def _sizes(p):
    heads, hd, n = p["heads"], p["head_dim"], p["d_state"]
    d_inner = heads * hd
    return heads, hd, n, d_inner, d_inner + 2 * p.get("n_groups", 1) * n


def _mamba_infer(layer: Layer):
    x = layer.inputs[0].spec
    p = layer.params
    heads, _hd, _n, d_inner, conv_dim = _sizes(p)
    groups = p.get("n_groups", 1)
    if groups < 1 or heads % groups:
        raise ValueError(f"mamba2: {groups} groups over {heads} heads")
    d = x.shape[-1]
    f32 = DataType.FLOAT
    layer.weight_specs = {
        "in_proj": TensorSpec((d, d_inner + conv_dim + heads), x.dtype),
        "conv_w": TensorSpec((p["d_conv"], conv_dim), x.dtype),
        "bias_conv": TensorSpec((conv_dim,), x.dtype),
        "A_log": TensorSpec((heads,), f32),
        "D": TensorSpec((heads,), f32),
        "dt_bias": TensorSpec((heads,), f32),
        "norm": TensorSpec((d_inner,), x.dtype),
        "out_proj": TensorSpec((d_inner, d), x.dtype),
    }
    return [x]


# the XLA form takes a long input through its chunks in blocks of about this
# many tokens (lax.map over groups of rows), so that the f32 `[rows, heads,
# chunk, chunk]` intermediates it streams through HBM stay a fraction of a
# prefill wave's. The kernel holds them on the chip and takes the wave whole.
MAMBA_TOKEN_BLOCK = 4096


def _ssd_xla(u, dt, a, bm, cm, chunk: int):
    """`ssd_scan` in plain XLA (jax.numpy), differentiable by JAX; bm, cm
    `[b, L, G, N]`. Head-major from the start (the decay mask is born `[..,
    H, l, s]` from a `[.., H, q]` cumulative sum: the batch of its product
    with `u` leads and nothing is relaid), the groups folded into the batch,
    and everything that depends on no other chunk (`C B^T`, the mask, the
    masked product, each chunk's own contribution to the state) batched over
    the chunks; only the `[chunks]`-long recurrence of the states is
    sequential."""
    b, length, heads, hd = u.shape
    g, n = bm.shape[2:]
    per = heads // g
    rows = max(1, MAMBA_TOKEN_BLOCK // length)
    if b > rows and b % rows == 0:
        y, state = jax.lax.map(
            lambda xs: _ssd_xla(xs[0], xs[1], a, xs[2], xs[3], chunk),
            tuple(t.reshape((b // rows, rows) + t.shape[1:])
                  for t in (u, dt, bm, cm)))
        return y.reshape((b,) + y.shape[2:]), state.reshape((b,) + state.shape[2:])
    q = min(int(chunk), length)
    pad = -length % q
    if pad:     # steps with dt = 0 and u = 0: the state stays, y is unused
        u, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (u, dt, bm, cm))
    nc = (length + pad) // q
    dot = u.dtype
    u = u.reshape(b, nc, q, g, per, hd)
    bm, cm = (t.reshape(b, nc, q, g, n) for t in (bm, cm))

    def by_position(t):     # [b, nc, G, H / G, q] -> [b, nc, q, G, H / G, 1]
        return jnp.transpose(t, (0, 1, 4, 2, 3))[..., None]

    dt = jnp.transpose(dt.reshape(b, nc, q, g, per), (0, 1, 3, 4, 2))
    cs = jnp.cumsum(dt * a.reshape(g, per, 1), axis=-1)     # [b, nc, G, H/G, q]
    seg = cs[..., :, None] - cs[..., None, :]               # [.., l, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg, -jnp.inf))
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cm, bm,
                    preferred_element_type=jnp.float32)
    m = cb[:, :, :, None] * decay * dt[..., None, :]        # [b, nc, G, H/G, l, s]
    y = jnp.einsum("bcghls,bcsghp->bclghp", m.astype(dot), u,
                   preferred_element_type=jnp.float32)
    w = jnp.exp(cs[..., -1:] - cs) * dt                     # [b, nc, G, H/G, q]
    own = jnp.einsum("bcsghp,bcsgn->bcghpn", (by_position(w) * u).astype(dot),
                     bm, preferred_element_type=jnp.float32)

    def carry(state, xs):   # -> the state after the chunk; emits the one before
        own_c, decay_c = xs
        return state * decay_c[..., None, None] + own_c, state

    state, before = jax.lax.scan(
        carry, jnp.zeros((b, g, per, hd, n), jnp.float32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(cs[..., -1]), 1, 0)))
    y = y + jnp.einsum("bclgn,cbghpn->bclghp", cm.astype(jnp.float32), before) \
        * by_position(jnp.exp(cs))
    return (y.reshape(b, nc * q, heads, hd)[:, :length],
            state.reshape(b, heads, hd, n))


def _mixer_xla(u, z, dt, a, bm, cm, d_skip, norm, chunk, eps, z_column):
    """`mixer_scan` in plain XLA: the scan, the skip `y + D u`, the gate and
    the norm; z is columns `z_column` on of the array handed in."""
    b, s, heads, hd = u.shape
    d_inner = heads * hd
    y, state = _ssd_xla(u, dt, a, bm, cm, chunk)
    y = y.reshape(b, s, d_inner) + jnp.repeat(d_skip, hd) \
        * u.reshape(b, s, d_inner).astype(jnp.float32)
    return _gated(y, z[..., z_column:z_column + d_inner], norm, bm.shape[2],
                  eps, u.dtype), state


# The kernel forward, the XLA form's gradient (recomputed: no cell trains a
# Mamba layer yet, so the backward's speed is nobody's). Static: the chunk,
# the tile `(q, hs)` and, gated, eps and z's first column.
def _ssd_forward(u, dt, a, bm, cm, chunk, tiles):
    return ssd_chunk_scan(u, dt, a, bm, cm, *tiles)


def _ssd_backward(chunk, tiles, operands, g):
    return jax.vjp(lambda *t: _ssd_xla(*t, chunk), *operands)[1](g)


_ssd_kernel = jax.custom_vjp(_ssd_forward, nondiff_argnums=(5, 6))
_ssd_kernel.defvjp(lambda *args: (_ssd_forward(*args), args[:5]), _ssd_backward)


def _mixer_forward(u, z, dt, a, bm, cm, d_skip, norm, chunk, eps, z_column,
                   tiles):
    return ssd_chunk_scan_gated(u, dt, a, bm, cm, z, d_skip, norm, *tiles,
                                eps=eps, z_column=z_column)


def _mixer_backward(chunk, eps, z_column, tiles, operands, g):
    return jax.vjp(lambda *t: _mixer_xla(*t, chunk, eps, z_column),
                   *operands)[1](g)


_mixer_kernel = jax.custom_vjp(_mixer_forward, nondiff_argnums=(8, 9, 10, 11))
_mixer_kernel.defvjp(lambda *args: (_mixer_forward(*args), args[:8]),
                     _mixer_backward)


def _tiles(u, bm, chunk):
    _b, length, heads, hd = u.shape
    groups, n = bm.shape[2:]
    return scan_tiles(length, heads, hd, n, groups, u.dtype.itemsize, chunk)


def scan_path(u, bm, chunk: int) -> dict:
    """Which form the scan of u `[b, L, H, P]` and B `[b, L, G, N]` (arrays
    or their shapes and types) takes: `{"path": "kernel", "tile": q,
    "head_block": hs}` or `{"path": "xla", "tile": chunk}`."""
    tiles = _tiles(u, bm, chunk)
    if tiles is None:
        return {"path": "xla", "tile": min(int(chunk), u.shape[1])}
    return {"path": "kernel", "tile": tiles[0], "head_block": tiles[1]}


def ssd_scan(u, dt, a, bm, cm, chunk: int):
    """The recurrence S_t = exp(dt_t a) S_{t-1} + dt_t u_t (x) B_t, y_t =
    S_t C_t from S_0 = 0, by chunks. u [b, L, H, P]; dt [b, L, H] f32, >= 0;
    a [H] f32, < 0; bm, cm [b, L, N], or [b, L, G, N] where the heads read
    them in G groups (head h those of group h // (H / G)). Returns (y [b, L,
    H, P] f32, the state after step L [b, H, P, N] f32). Products take their
    operands in u's dtype and accumulate in f32; decays are f32.

    One algorithm for every caller, the form chosen from the shapes
    (`scan_path`): the kernel where `scan_tiles` takes them (whole lanes of
    heads and state), the XLA form elsewhere and for the kernel's backward.
    The result is the recurrence's at any chunk, so `chunk` bounds a tile
    and is not mathematics."""
    if bm.ndim == 3:
        bm, cm = bm[:, :, None], cm[:, :, None]
    tiles = _tiles(u, bm, chunk)
    if tiles is None:
        return _ssd_xla(u, dt, a, bm, cm, int(chunk))
    return _ssd_kernel(u, dt, a, bm, cm, int(chunk), tiles)


def mixer_scan(u, z, dt, a, bm, cm, d_skip, norm, chunk: int, eps: float,
               z_column: int = 0):
    """`ssd_scan`, then the skip, the gate and the norm, RMS_G((y + D u) *
    silu(z); norm): (`[b, L, H P]` in u's type, the last state). z is read
    from column `z_column` on of the array handed in (the whole `[z | xBC |
    dt]` will do). The same choice of form as `ssd_scan`; the kernel does
    all of it on the tile it holds, so no f32 `[b, L, H P]` value exists."""
    if bm.ndim == 3:
        bm, cm = bm[:, :, None], cm[:, :, None]
    tiles = _tiles(u, bm, chunk)
    if tiles is None:
        return _mixer_xla(u, z, dt, a, bm, cm, d_skip, norm, int(chunk), eps,
                          z_column)
    return _mixer_kernel(u, z, dt, a, bm, cm, d_skip, norm, int(chunk),
                         float(eps), int(z_column), tiles)


def _gated(y, z, norm, groups: int, eps: float, dt):
    """RMS(y * silu(z); norm), in the compute type `dt`; over each of the
    `groups` groups apart where there is more than one."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    if groups == 1:
        return rms_norm(g, norm, eps).astype(dt)
    split = g.shape[:-1] + (groups, g.shape[-1] // groups)
    return rms_norm(g.reshape(split), norm.reshape(split[-2:]),
                    eps).reshape(g.shape).astype(dt)


def _b_and_c(act, p, lead):
    """B and C out of the activated xBC `[*lead, conv_dim]`: `[*lead, N]`
    each, or `[*lead, G, N]` for a layer of G > 1 groups."""
    _heads, _hd, n, d_inner, _conv_dim = _sizes(p)
    groups = p.get("n_groups", 1)
    shape = lead + ((groups, n) if groups > 1 else (n,))
    return (act[..., d_inner:d_inner + groups * n].reshape(shape),
            act[..., d_inner + groups * n:].reshape(shape))


def conv_tail(x, valid, k: int):
    """Each row's last k-1 REAL rows of x `[b, s, C]` (zeros before the
    sequence's start): what a decode step's causal conv of width k needs of
    the prompt. `valid` `[b, s]` bool is a right-padded prefix of ones."""
    s = x.shape[1]
    lengths = jnp.sum(valid, axis=1).astype(jnp.int32)
    idx = lengths[:, None] - (k - 1) + jnp.arange(k - 1)[None, :]
    tail = jnp.take_along_axis(x, jnp.clip(idx, 0, s - 1)[..., None], axis=1)
    return jnp.where(idx[..., None] >= 0, tail, 0)


def _report_state_bytes(ctx: LoweringCtx, valid, st) -> None:
    """`ssm_state_bytes`: both leaves of the live slots' state, read and
    written by this step."""
    ctx.add_stat("ssm_state_bytes", jnp.sum(valid).astype(jnp.float32)
                 * (2.0 * sum(leaf[0].nbytes for leaf in st.values())))


def _report_step_kernel(ctx: LoweringCtx, slots) -> None:
    """`ssm_step_kernel_slots`: the step kernel's grid steps on its first
    axis (where it ran: the live slots `ssm_state_bytes` counts), 0 where
    the step took the XLA form."""
    ctx.add_stat("ssm_step_kernel_slots", slots)


def step_path(heads: int, head_dim: int, d_state: int, groups: int,
              mesh=None) -> dict:
    """Which form a decode step's recurrence takes, from the shapes and the
    mesh the program is lowered for: what a lowered layer reports in its
    `ssm/step_path` span. `{"path": "kernel", "head_block": hs, "groups":
    G}` or `{"path": "xla", "groups": G}`. On a mesh of several devices the
    XLA form stays: GSPMD partitions it, and cannot partition a Mosaic
    call."""
    hs = None if multi_device(mesh) \
        else step_kernel.head_block(heads, head_dim, d_state, groups)
    if hs is None:
        return {"path": "xla", "groups": groups}
    return {"path": "kernel", "head_block": hs, "groups": groups}


def _step_xla(state, dt1, a, u, b_t, c_t):
    """`ssm_step` in plain XLA: one pass over all the slots' state."""
    heads = state.shape[1]
    if b_t.ndim == 3:   # a head's B and C are its group's: [b, H, N]
        b_t, c_t = (jnp.repeat(t, heads // t.shape[1], axis=1)
                    for t in (b_t, c_t))
        ssm = state * jnp.exp(dt1 * a)[:, :, None, None] \
            + (dt1[..., None] * u)[..., None] * b_t[:, :, None, :]
        return jnp.einsum("bhpn,bhn->bhp", ssm, c_t), ssm
    ssm = state * jnp.exp(dt1 * a)[:, :, None, None] \
        + (dt1[..., None] * u)[..., None] * b_t[:, None, None, :]
    return jnp.einsum("bhpn,bn->bhp", ssm, c_t), ssm


def ssm_step(state, dt1, a, u, b_t, c_t, live, path: dict):
    """One step of the recurrence: state `[b, H, P, N]` f32 as it lies at
    rest (donated), dt1 `[b, H]` f32 (0 for a slot that is not live), a `[H]`
    f32, u `[b, H, P]` f32, b_t and c_t `[b, N]` or `[b, G, N]` f32, live
    `[b]` bool, `path` as `step_path` says -> (y = S C of the NEW state `[b,
    H, P]` f32, the new state, the slots the kernel took).

    One result for every live slot in either form: the kernel (a live
    slot's state passes through the chip once, in place; a slot that is not
    live is not touched and reads y = 0) or the XLA form (a slot that is not
    live is rewritten as it was; its y is a read-out nobody reads)."""
    if path["path"] == "kernel":
        b, _heads, _hd, n = state.shape
        y, state = step_kernel.mamba2_step(
            state, jnp.exp(dt1 * a), dt1[..., None] * u,
            b_t.reshape(b, -1, n), c_t.reshape(b, -1, n), live,
            path["head_block"])
        return y, state, jnp.sum(live.astype(jnp.int32))
    return _step_xla(state, dt1, a, u, b_t, c_t) + (jnp.int32(0),)


def _mamba_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x = inputs[0]
    p = layer.params
    heads, hd, n, d_inner, conv_dim = _sizes(p)
    k = p["d_conv"]
    groups = p.get("n_groups", 1)
    dt_ = x.dtype
    b, s, _d = x.shape
    valid = (inputs[1] > 0) if len(inputs) > 1 else jnp.ones((b, s), bool)
    a = -jnp.exp(weights["A_log"].astype(jnp.float32))
    d_skip = weights["D"].astype(jnp.float32)
    conv_w = weights["conv_w"].astype(jnp.float32)
    conv_b = weights["bias_conv"].astype(jnp.float32)

    zxbcdt = x @ weights["in_proj"].astype(dt_)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = jax.nn.softplus(zxbcdt[..., d_inner + conv_dim:].astype(jnp.float32)
                         + weights["dt_bias"].astype(jnp.float32))
    dt = jnp.where(valid[..., None], dt, 0.0)                   # [b, s, H]

    if p.get("mode") == "decode":
        if s != 1:
            raise NotImplementedError(
                "mamba2 decode takes one token a step (a verify pass over "
                "several would have to roll the state back)")
        st = ctx.state[layer.name]
        window = jnp.concatenate([st["conv"], xbc.astype(st["conv"].dtype)],
                                 axis=1)                        # [b, k, C]
        conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), conv_w)
        act = jax.nn.silu(conv + conv_b)
        u = act[:, :d_inner].reshape(b, heads, hd)
        b_t, c_t = _b_and_c(act, p, (b,))
        path = step_path(heads, hd, n, groups, ctx.mesh)
        # one span a lowered layer (trace time): the form its step took
        with tel.span("ssm/step_path", cat="compile", layer=layer.name,
                      **path):
            y, ssm, kernel_slots = ssm_step(st["ssm"], dt[:, 0], a, u, b_t,
                                            c_t, valid[:, 0], path)
        y = y + d_skip[None, :, None] * u
        ctx.new_state[layer.name] = {
            "ssm": ssm,
            "conv": jnp.where(valid[:, :1, None], window[:, 1:], st["conv"])}
        _report_state_bytes(ctx, valid, st)
        _report_step_kernel(ctx, kernel_slots)
        g = _gated(y.reshape(b, 1, d_inner), z, weights["norm"], groups,
                   p.get("eps", 1e-5), dt_)
        return [g @ weights["out_proj"].astype(dt_)]

    # causal depthwise conv: out[t] = sum_j w[j] x[t - k + 1 + j]
    xp = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = sum(xp[:, j:j + s].astype(jnp.float32) * conv_w[j] for j in range(k))
    act = jax.nn.silu(conv + conv_b)
    u = act[..., :d_inner].reshape(b, s, heads, hd).astype(dt_)
    b_m, c_m = (t.astype(dt_).reshape(b, s, groups, n)
                for t in _b_and_c(act, p, (b, s)))
    chunk = p.get("chunk", 256)
    # one span a lowered layer (trace time): the form its scan took
    with tel.span("ssm/scan_path", cat="compile", layer=layer.name,
                  **scan_path(u, b_m, chunk)):
        g, ssm = mixer_scan(u, zxbcdt, dt, a, b_m, c_m, d_skip,
                            weights["norm"], chunk, p.get("eps", 1e-5))
    if p.get("mode") == "state_out":
        # the tail is taken now, with the layer's output: left to the
        # scheduler it is taken at the program's end, and every layer's xBC
        # (a quarter GB each in a prefill wave) stays live until then
        tail = conv_tail(xbc, valid, k)
        out, tail = jax.lax.optimization_barrier(
            (g @ weights["out_proj"].astype(dt_), tail))
        ctx.hand_out_slot_state(layer.name, {"ssm": ssm, "conv": tail},
                                valid)
        return [out]
    return [g @ weights["out_proj"].astype(dt_)]


def _mamba_flops(layer: Layer):
    """Forward: the two projections, and the recurrence's own products (the
    state update and the read-out, 2 * 2 * P * N a head and token). What
    the chunked form multiplies besides, in either of its forms (the masked
    product, 2 * tile * P a head and token, as much again at tile 256 and P
    64; `C B^T`, 2 * tile * N a group and token), is not counted: it is the
    algorithm's price, not the layer's need."""
    x = layer.inputs[0].spec
    heads, hd, n, d_inner, conv_dim = _sizes(layer.params)
    tokens = x.num_elements // x.shape[-1]
    proj = x.shape[-1] * (d_inner + conv_dim + heads) + d_inner * x.shape[-1]
    return 2.0 * tokens * proj + 4.0 * tokens * heads * hd * n


def _mamba_serving_params(params: dict, kind: str) -> dict:
    return dict(params, mode="decode" if kind == "decode" else "state_out")


def _mamba_slot_state(layer: Layer) -> dict:
    heads, hd, n, _d_inner, conv_dim = _sizes(layer.params)
    return {"ssm": ((heads, hd, n), jnp.float32),
            "conv": ((layer.params["d_conv"] - 1, conv_dim),
                     layer.inputs[0].spec.dtype.jnp_dtype)}


register_op(OperatorType.MAMBA2, _mamba_infer, _mamba_lower, _mamba_flops,
            serving_params=_mamba_serving_params, state_kind="recurrent",
            slot_state=_mamba_slot_state,
            span_facts=lambda layer: {
                "ssm_groups": layer.params.get("n_groups", 1)})
