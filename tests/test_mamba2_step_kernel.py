"""A Mamba-2 decode step's recurrence is one kernel over the live slots
(kernels/mamba2_step.py behind ops/ssm_ops.py: `ssm_step`): against the XLA
lines every step took before (`ssm_ops._step_xla`) on the same inputs, at
lane-aligned toy widths, the kernel interpreted.

Tolerance. Both forms compute `decay * S + (dt u) (x) B` in float32 with the
same three operations an element, so the new state differs by an ulp at most
where the compiler contracts a product and a sum; the read-out sums 128
terms in another order: RTOL 1e-5 of the result's scale (a wrong group, head
or slot is off by the size of a row). A slot that is not live is not
touched: its state is BIT-identical to what went in, and its y is 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels import mamba2_step
from flexflow_tpu.ops import get_op_def, ssm_ops
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx

SLOTS, N = 16, 128
RTOL = 1e-5
LIVE = {"none": [], "some": [1, 6, 7, 13], "all": list(range(SLOTS))}
# name: (heads, head_dim, groups, heads a grid step)
SHAPES = {"one_group_whole_slot": (8, 8, 1, 8),
          "one_group_head_blocks": (32, 8, 1, 8),
          "groups_whole_slot": (32, 8, 4, 32),
          "groups_block_inside_a_group": (32, 8, 2, 8),
          "groups_block_over_two_groups": (32, 8, 4, 16),
          "served_head": (16, 64, 2, 8)}


def operands(heads, hd, groups, live, seed=0):
    rng = np.random.default_rng(seed)
    mask = np.zeros(SLOTS, bool)
    mask[live] = True
    state = rng.normal(size=(SLOTS, heads, hd, N)).astype(np.float32)
    # as the op hands them over: dt = 0 at a slot that is not live
    dt1 = np.where(mask[:, None], rng.uniform(0.01, 1.0, (SLOTS, heads)), 0.0)
    a = -rng.uniform(0.5, 2.0, heads)
    u = rng.normal(size=(SLOTS, heads, hd))
    bc = (SLOTS, N) if groups == 1 else (SLOTS, groups, N)
    b_t, c_t = rng.normal(size=bc), rng.normal(size=bc)
    return [jnp.asarray(t, jnp.float32) for t in (state, dt1, a, u, b_t, c_t)] \
        + [jnp.asarray(mask)]


def block_of(monkeypatch, heads, hd, groups, hs):
    """The budget under which `head_block` gives `hs` heads a grid step."""
    monkeypatch.setattr(mamba2_step, "_BLOCK_BYTES", hs * hd * N * 4)
    path = ssm_ops.step_path(heads, hd, N, groups)
    assert path["path"] == "kernel" and path["head_block"] == hs
    return path


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_step_kernel_against_the_xla_lines(shape, live, monkeypatch):
    heads, hd, groups, hs = SHAPES[shape]
    args = operands(heads, hd, groups, LIVE[live])
    state, mask = np.asarray(args[0]), np.asarray(args[-1])
    path = block_of(monkeypatch, heads, hd, groups, hs)
    y, new, slots = ssm_ops.ssm_step(*args, path)
    want_y, want_new, none = ssm_ops.ssm_step(*args, {"path": "xla"})
    assert int(slots) == len(LIVE[live]) and int(none) == 0
    y, new, want_y, want_new = (np.asarray(t) for t in (y, new, want_y,
                                                        want_new))
    assert y.shape == want_y.shape and new.shape == want_new.shape
    if mask.any():
        for got, want in ((y, want_y), (new, want_new)):
            assert np.abs(got[mask] - want[mask]).max() \
                <= RTOL * np.abs(want[mask]).max()
        assert np.abs(y[mask]).max() > 1.0      # something was read out
        assert np.abs(new[mask] - state[mask]).max() > 0.1
    # a slot that is not live: not a byte of its state moved, and y is 0
    assert np.array_equal(new[~mask].view(np.uint32),
                          state[~mask].view(np.uint32))
    assert not y[~mask].any()


def test_a_slot_that_is_not_live_is_never_read(monkeypatch):
    """NaN in a dead slot's state and operands: nothing of it reaches a live
    slot's result, and it is handed back as it came."""
    heads, hd, groups, hs = SHAPES["groups_block_inside_a_group"]
    args = operands(heads, hd, groups, LIVE["some"])
    mask = np.asarray(args[-1])
    poison = lambda t: jnp.where(                               # noqa: E731
        jnp.asarray(mask).reshape((SLOTS,) + (1,) * (t.ndim - 1)), t, jnp.nan)
    clean = ssm_ops.ssm_step(*args, block_of(monkeypatch, heads, hd, groups, hs))
    state, dt1, a, u, b_t, c_t, live = args
    y, new, _ = ssm_ops.ssm_step(poison(state), dt1, a, poison(u),
                                 poison(b_t), poison(c_t), live,
                                 block_of(monkeypatch, heads, hd, groups, hs))
    assert np.array_equal(np.asarray(y), np.asarray(clean[0]))
    assert np.array_equal(np.asarray(new)[mask], np.asarray(clean[1])[mask])
    assert np.isnan(np.asarray(new)[~mask]).all()


@pytest.mark.parametrize("shape", [
    # (heads, head_dim, d_state, groups) -> the path, the heads a grid step
    ("granite", (128, 64, 128, 1), "kernel", 64),
    ("nemotron", (128, 64, 128, 8), "kernel", 64),
    ("a_state_of_two_slabs", (128, 64, 256, 1), "kernel", 32),
    ("heads_of_32", (256, 32, 128, 1), "kernel", 128),
    ("granite_tiny", (8, 16, 16, 1), "xla", None),
    ("nemotron_tiny", (8, 16, 16, 4), "xla", None),
    ("a_state_of_64", (128, 64, 64, 1), "xla", None),
    ("a_head_of_4", (16, 4, 128, 1), "xla", None),
    ("a_group_of_2_heads", (8, 16, 128, 4), "xla", None),
], ids=lambda s: s[0] if isinstance(s[0], str) else None)
def test_the_form_is_chosen_from_the_shapes(shape):
    _name, sizes, path, hs = shape
    got = ssm_ops.step_path(*sizes)
    assert got["path"] == path and got.get("head_block") == hs
    assert got["groups"] == sizes[3]
    if hs:
        heads, hd, n, _groups = sizes
        assert heads % hs == 0 and hs % mamba2_step._TURN == 0
        # in and out, twice each for the pipeline, under the stated limit
        assert 4 * hs * hd * n * 4 < mamba2_step._VMEM_LIMIT_BYTES


def mamba_layer(heads, hd, groups, d=64):
    ins = [Tensor(TensorSpec((SLOTS, 1, d), DataType.FLOAT), name="x"),
           Tensor(TensorSpec((SLOTS, 1), DataType.INT32), name="valid")]
    layer = Layer(OperatorType.MAMBA2,
                  {"heads": heads, "head_dim": hd, "d_state": N, "d_conv": 4,
                   "n_groups": groups, "mode": "decode"}, ins, name="mixer")
    get_op_def(OperatorType.MAMBA2).infer(layer)
    return layer


def lower_step(layer, x, valid, weights, state, stats=None, mesh=None):
    ctx = LoweringCtx(state={layer.name: state}, new_state={}, stats=stats,
                      mesh=mesh)
    out = get_op_def(OperatorType.MAMBA2).lower(layer, [x, valid], weights,
                                                ctx)[0]
    return out, ctx.new_state[layer.name]


def layer_operands(layer, live, seed=1):
    rng = np.random.default_rng(seed)
    weights = {n: jnp.asarray(rng.normal(size=s.shape) / np.sqrt(s.shape[0]),
                              jnp.float32)
               for n, s in layer.weight_specs.items()}
    shapes = get_op_def(OperatorType.MAMBA2).slot_state(layer)
    state = {k: jnp.asarray(rng.normal(size=(SLOTS,) + shape), dt)
             for k, (shape, dt) in shapes.items()}
    x = jnp.asarray(rng.normal(size=(SLOTS, 1, 64)), jnp.float32)
    mask = np.zeros((SLOTS, 1), np.int32)
    mask[live] = 1
    return x, jnp.asarray(mask), weights, state


@pytest.mark.parametrize("groups", (1, 2))
def test_the_layer_on_either_path_and_its_counters(groups, monkeypatch):
    """The whole decode branch of the op: the output and both leaves of the
    state against the same layer made to take the XLA lines, and the
    engagement counter beside `ssm_state_bytes`."""
    layer = mamba_layer(16, 8, groups)
    x, valid, weights, state = layer_operands(layer, LIVE["some"])
    mask = np.asarray(valid)[:, 0] > 0
    stats = {}
    got, new = lower_step(layer, x, valid, weights, state, stats)
    per_slot = sum(leaf[0].nbytes for leaf in state.values())
    assert float(stats["ssm_state_bytes"]) == 2 * mask.sum() * per_slot
    assert int(stats["ssm_step_kernel_slots"]) == mask.sum() \
        == float(stats["ssm_state_bytes"]) / (2 * per_slot)
    monkeypatch.setattr(mamba2_step, "head_block", lambda *a: None)
    theirs = {}
    want, want_new = lower_step(layer, x, valid, weights, state, theirs)
    assert int(theirs["ssm_step_kernel_slots"]) == 0
    assert theirs["ssm_state_bytes"] == stats["ssm_state_bytes"]
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got[mask] - want[mask]).max() <= RTOL * np.abs(want).max()
    assert np.abs(got[mask]).max() > 1e-2
    for leaf in ("ssm", "conv"):
        a, b = np.asarray(new[leaf]), np.asarray(want_new[leaf])
        assert np.abs(a - b).max() <= RTOL * np.abs(b).max()
        assert np.array_equal(a[~mask], np.asarray(state[leaf])[~mask])


def step_jaxpr(layer, mesh=None):
    x, valid, weights, state = layer_operands(layer, LIVE["some"])
    return str(jax.make_jaxpr(
        lambda x, w, st: lower_step(layer, x, valid, w, st, mesh=mesh))(
            x, weights, state))


def test_a_kernel_path_step_multiplies_no_whole_state_outside_it(monkeypatch):
    """The StableHLO of a step on the kernel path, lowered for the TPU: the
    Mosaic call by its name, and outside it no operation that makes a
    `[slots, H, P, N]` value (the XLA lines' multiplies, sum and read-out
    over all the slots are gone: the slot array goes into the call and comes
    out of it). The same step on the XLA lines holds them."""
    monkeypatch.setattr(mamba2_step, "_interpret", lambda: False)
    mamba2_step._call.clear_cache()
    layer = mamba_layer(16, 8, 2)
    x, valid, weights, state = layer_operands(layer, LIVE["some"])
    whole = f"tensor<{SLOTS}x16x8x{N}xf32>"

    def results_of_that_shape():
        text = jax.jit(
            lambda x, w, st: lower_step(layer, x, valid, w, st)).trace(
                x, weights, state).lower(lowering_platforms=("tpu",)).as_text()
        made = [line.split(" = ", 1)[1].split()[0]
                for line in text.splitlines()
                if " = " in line and line.rstrip().endswith(whole)
                or " = " in line and f"-> {whole}" in line]
        return text, [op for op in made if not op.startswith("call")]

    text, made = results_of_that_shape()
    assert "tpu_custom_call" in text and "ff_mamba2_step" in text
    assert not made, made
    monkeypatch.setattr(mamba2_step, "head_block", lambda *a: None)
    text, made = results_of_that_shape()
    assert "tpu_custom_call" not in text
    assert "stablehlo.multiply" in made and "stablehlo.add" in made
    mamba2_step._call.clear_cache()


def test_layers_of_one_shape_trace_the_kernel_once(monkeypatch):
    """`_call` is jitted with the shapes as its key: the five to nine mixers
    of a decode program trace and lower the body once."""
    calls = []
    kernel = mamba2_step._kernel
    monkeypatch.setattr(mamba2_step, "_kernel",
                        lambda *a, **k: calls.append(1) or kernel(*a, **k))
    mamba2_step._call.clear_cache()
    layer = mamba_layer(16, 8, 2)
    x, valid, weights, state = layer_operands(layer, LIVE["some"])

    def three(x, w, st):
        for _ in range(3):
            y, st = lower_step(layer, x, valid, w, st)
            x = x + y
        return x, st

    jax.jit(three).lower(x, weights, state)
    assert len(calls) == 1
    mamba2_step._call.clear_cache()


@pytest.mark.parametrize("case", ["a_tiny_state", "a_step_on_two_devices"])
def test_every_other_step_keeps_the_xla_lines(case):
    if case == "a_tiny_state":
        ins = [Tensor(TensorSpec((SLOTS, 1, 64), DataType.FLOAT), name="x"),
               Tensor(TensorSpec((SLOTS, 1), DataType.INT32), name="valid")]
        layer = Layer(OperatorType.MAMBA2,
                      {"heads": 8, "head_dim": 16, "d_state": 16, "d_conv": 4,
                       "mode": "decode"}, ins, name="mixer")
        get_op_def(OperatorType.MAMBA2).infer(layer)
        mesh = None
    else:
        # GSPMD cannot partition a Mosaic call (kernels/partition.py)
        layer = mamba_layer(16, 8, 2)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("data",))
        assert ssm_ops.step_path(16, 8, N, 2, mesh)["path"] == "xla"
        assert ssm_ops.step_path(16, 8, N, 2)["path"] == "kernel"
    jaxpr = step_jaxpr(layer, mesh)
    assert "pallas_call" not in jaxpr and "dot_general" in jaxpr
