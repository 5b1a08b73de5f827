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
layer with G = 1 lowers to the program it lowered to before groups existed.

Three forms of one op, chosen by `params["mode"]`:

- None (training, evaluation): the whole sequence by chunks of
  `params["chunk"]` (the SSD form: inside a chunk the recurrence is one
  masked matrix product, between chunks the state is carried by a
  `lax.scan`, so the `[chunk, chunk]` intermediates exist for one chunk at
  a time). Equal to the recurrence at any length; a length that is no
  multiple of the chunk is padded with steps that change nothing.
- "state_out" (serving prefill): the same, and the state is handed out in
  `ctx.new_state[layer.name] = {"ssm": [b, H, P, N] f32, "conv": [b,
  d_conv - 1, conv_dim]}`.
- "decode" (serving decode): one step of the recurrence on
  `ctx.state[layer.name]`, written back to `ctx.new_state`. Reports
  (ctx.add_stat) `ssm_state_bytes`: the state the step's live slots read
  and wrote (both leaves, twice).

The second input, `valid` `[b, s]` (int, 1 = a real token), says which
positions exist: at the others `dt` is 0 (the state neither decays nor
takes anything in) and nothing enters the conv tail. A right-padded prompt
wave therefore hands out each row's state after its LAST REAL token, and a
decode step advances only the slots that `valid` names. Without the input
every position is real.

Plain XLA (jax.numpy); gradients come from JAX.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.dtype import DataType
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


def ssd_scan(u, dt, a, bm, cm, chunk: int):
    """The recurrence S_t = exp(dt_t a) S_{t-1} + dt_t u_t (x) B_t, y_t =
    S_t C_t from S_0 = 0, by chunks. u [b, L, H, P]; dt [b, L, H] f32, >= 0;
    a [H] f32, < 0; bm, cm [b, L, N], or [b, L, G, N] where the heads read
    them in G groups (head h those of group h // (H / G)). Returns (y [b, L,
    H, P] f32, the state after step L [b, H, P, N] f32). Products take their
    operands in u's dtype and accumulate in f32; decays are f32."""
    b, length, heads, hd = u.shape
    n = bm.shape[-1]
    if bm.ndim == 4:    # the same scan, a group's heads with their B and C
        g = bm.shape[2]

        def split(t):   # [b, L, H, ...] -> [b, L, G, H / G, ...]
            return t.reshape(t.shape[:2] + (g, heads // g) + t.shape[3:])

        y, state = jax.vmap(
            lambda *group: ssd_scan(*group, chunk),
            in_axes=(2, 2, 0, 2, 2), out_axes=(2, 1))(
                split(u), split(dt), a.reshape(g, heads // g), bm, cm)
        return (y.reshape(b, length, heads, hd),
                state.reshape(b, heads, hd, n))
    q = min(int(chunk), length)
    pad = -length % q
    if pad:     # steps with dt = 0 and u = 0: the state stays, y is unused
        u, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (u, dt, bm, cm))
    nc = (length + pad) // q
    dot = u.dtype

    def chunks(t):      # [b, nc * q, ...] -> [nc, b, q, ...]
        return jnp.moveaxis(t.reshape((b, nc, q) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def body(state, xs):
        u_c, dt_c, b_c, c_c = xs
        cs = jnp.cumsum(dt_c * a, axis=1)                       # [b, q, H]
        seg = cs[:, :, None, :] - cs[:, None, :, :]             # [b, l, s, H]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bln,bsn->bls", c_c, b_c,
                        preferred_element_type=jnp.float32)
        m = cb[..., None] * decay * dt_c[:, None, :, :]         # [b, l, s, H]
        y = jnp.einsum("blsh,bshp->blhp", m.astype(dot), u_c,
                       preferred_element_type=jnp.float32)
        y = y + jnp.einsum("bln,bhpn->blhp", c_c.astype(jnp.float32), state) \
            * jnp.exp(cs)[..., None]
        w = jnp.exp(cs[:, -1:, :] - cs) * dt_c                  # [b, q, H]
        state = state * jnp.exp(cs[:, -1, :])[:, :, None, None] + jnp.einsum(
            "bshp,bsn->bhpn", (w[..., None] * u_c).astype(dot), b_c,
            preferred_element_type=jnp.float32)
        return state, y

    state0 = jnp.zeros((b, heads, hd, n), jnp.float32)
    state, ys = jax.lax.scan(body, state0,
                             (chunks(u), chunks(dt), chunks(bm), chunks(cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * q, heads, hd)
    return y[:, :length], state


# rows of a long input go through the conv, the scan and the gate in blocks
# of about this many tokens (lax.map over groups of rows), so that their f32
# intermediates stay a fraction of a prefill wave's; the two projections
# stay whole
MAMBA_TOKEN_BLOCK = 4096


def _gated(y, z, weights, p, dt):
    """RMS(y * silu(z); w_norm), in the compute type; over each of the
    layer's groups apart where it has more than one."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    groups = p.get("n_groups", 1)
    if groups == 1:
        return rms_norm(g, weights["norm"], p.get("eps", 1e-5)).astype(dt)
    split = g.shape[:-1] + (groups, g.shape[-1] // groups)
    return rms_norm(g.reshape(split), weights["norm"].reshape(split[-2:]),
                    p.get("eps", 1e-5)).reshape(g.shape).astype(dt)


def _b_and_c(act, p, lead):
    """B and C out of the activated xBC `[*lead, conv_dim]`: `[*lead, N]`
    each, or `[*lead, G, N]` for a layer of G > 1 groups."""
    _heads, _hd, n, d_inner, _conv_dim = _sizes(p)
    groups = p.get("n_groups", 1)
    shape = lead + ((groups, n) if groups > 1 else (n,))
    return (act[..., d_inner:d_inner + groups * n].reshape(shape),
            act[..., d_inner + groups * n:].reshape(shape))


def _report_state_bytes(ctx: LoweringCtx, valid, st) -> None:
    """`ssm_state_bytes`: both leaves of the live slots' state, read and
    written by this step."""
    ctx.add_stat("ssm_state_bytes", jnp.sum(valid).astype(jnp.float32)
                 * (2.0 * sum(leaf[0].nbytes for leaf in st.values())))


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
        dt1 = dt[:, 0]                                          # [b, H]
        if groups > 1:  # a head's B and C are its group's: [b, H, N]
            b_t, c_t = (jnp.repeat(t, heads // groups, axis=1)
                        for t in (b_t, c_t))
            ssm = st["ssm"] * jnp.exp(dt1 * a)[:, :, None, None] \
                + (dt1[..., None] * u)[..., None] * b_t[:, :, None, :]
            y = jnp.einsum("bhpn,bhn->bhp", ssm, c_t)
        else:
            ssm = st["ssm"] * jnp.exp(dt1 * a)[:, :, None, None] \
                + (dt1[..., None] * u)[..., None] * b_t[:, None, None, :]
            y = jnp.einsum("bhpn,bn->bhp", ssm, c_t)
        y = y + d_skip[None, :, None] * u
        ctx.new_state[layer.name] = {
            "ssm": ssm,
            "conv": jnp.where(valid[:, :1, None], window[:, 1:], st["conv"])}
        _report_state_bytes(ctx, valid, st)
        g = _gated(y.reshape(b, 1, d_inner), z, weights, p, dt_)
        return [g @ weights["out_proj"].astype(dt_)]

    def core(rows):
        """Conv, scan, skip and gate of some rows: (gated [r, s, d_inner],
        the state after each row's last step [r, H, P, N])."""
        z_r, xbc_r, dt_r = rows
        r = z_r.shape[0]
        # causal depthwise conv: out[t] = sum_j w[j] x[t - k + 1 + j]
        xp = jnp.pad(xbc_r, [(0, 0), (k - 1, 0), (0, 0)]).astype(jnp.float32)
        conv = sum(xp[:, j:j + s] * conv_w[j] for j in range(k))
        act = jax.nn.silu(conv + conv_b)
        u = act[..., :d_inner].reshape(r, s, heads, hd).astype(dt_)
        b_m, c_m = (t.astype(dt_) for t in _b_and_c(act, p, (r, s)))
        y, ssm = ssd_scan(u, dt_r, a, b_m, c_m, p.get("chunk", 256))
        y = y + d_skip[None, None, :, None] * u.astype(jnp.float32)
        return _gated(y.reshape(r, s, d_inner), z_r, weights, p, dt_), ssm

    rows = max(1, MAMBA_TOKEN_BLOCK // s)
    if b > rows and b % rows == 0:
        def blocks(t):
            return t.reshape((b // rows, rows) + t.shape[1:])

        g, ssm = jax.lax.map(core, (blocks(z), blocks(xbc), blocks(dt)))
        g, ssm = g.reshape(b, s, d_inner), ssm.reshape((b,) + ssm.shape[2:])
    else:
        g, ssm = core((z, xbc, dt))
    if p.get("mode") == "state_out":
        # the conv tail: each row's last k-1 REAL xBC rows (zeros before the
        # sequence's start); `valid` is a right-padded prefix of ones
        lengths = jnp.sum(valid, axis=1).astype(jnp.int32)
        idx = lengths[:, None] - (k - 1) + jnp.arange(k - 1)[None, :]
        tail = jnp.take_along_axis(xbc, jnp.clip(idx, 0, s - 1)[..., None],
                                   axis=1)
        tail = jnp.where(idx[..., None] >= 0, tail, 0)
        # the tail is taken now, with the layer's output: left to the
        # scheduler it is taken at the program's end, and every layer's xBC
        # (a quarter GB each in a prefill wave) stays live until then
        out, tail = jax.lax.optimization_barrier(
            (g @ weights["out_proj"].astype(dt_), tail))
        ctx.new_state[layer.name] = {"ssm": ssm, "conv": tail}
        return [out]
    return [g @ weights["out_proj"].astype(dt_)]


def _mamba_flops(layer: Layer):
    """Forward: the two projections, and the recurrence's own products (the
    state update and the read-out, 2 * 2 * P * N a head and token; the
    chunked form does more, which is not counted)."""
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
            slot_state=_mamba_slot_state)
