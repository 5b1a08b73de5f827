"""The compiled train step's optimizer update against a plain reference.

`apply_update` (compiler/compile.py) is `tx.update` + `optax.apply_updates`
and nothing else: XLA fuses the chain into one pass per parameter leaf. What
guards it is not a second implementation inside the program but a NumPy one
here: three steps of `cm.train_step` on a two-layer linear model under
mean-squared error (so the gradients are four lines of NumPy too), parameter
leaves of odd sizes (33x65, 65, 65x7: no multiple of a lane or a tile),
every optimizer configuration the repo builds. The state keeps optax's own
tree (checkpoints and ZeRO's sharding constraints address it by that
layout) and the moments the dtype the optimizer states.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer

BATCH, D_IN, D_HID, D_OUT = 16, 33, 65, 7

OPTS = [
    pytest.param(AdamOptimizer(alpha=1e-3), id="adam"),
    pytest.param(AdamOptimizer(alpha=1e-3, weight_decay=0.01), id="adamw"),
    pytest.param(AdamOptimizer(alpha=1e-3, state_dtype="bfloat16"),
                 id="adam-bf16"),
    pytest.param(AdamOptimizer(alpha=1e-3, weight_decay=0.01,
                               state_dtype="bfloat16"), id="adamw-bf16"),
    pytest.param(SGDOptimizer(lr=0.05), id="sgd"),
    pytest.param(SGDOptimizer(lr=0.05, momentum=0.9, weight_decay=0.01),
                 id="sgd-momentum-wd"),
    pytest.param(SGDOptimizer(lr=0.05, momentum=0.9, nesterov=True),
                 id="sgd-nesterov"),
]


def _map(fn, *trees):
    return jax.tree_util.tree_map(fn, *trees)


def _gradients(p, x, t):
    """d mean((x W1 + b1) W2 - t)^2 / d (W1, b1, W2), in float32."""
    h = x @ p["fc"]["kernel"] + p["fc"]["bias"]
    dy = 2.0 * (h @ p["head"]["kernel"] - t) / np.float32(t.size)
    dh = dy @ p["head"]["kernel"].T
    return {"fc": {"kernel": x.T @ dh, "bias": dh.sum(0)},
            "head": {"kernel": h.T @ dy}}


class _Adam:
    def __init__(self, opt, params):
        self.o, self.count = opt, 0
        self.dtype = jnp.dtype(opt.state_dtype)          # ml_dtypes' bfloat16
        self.mu = _map(lambda p: np.zeros_like(p, self.dtype), params)
        self.nu = _map(lambda p: np.zeros_like(p, self.dtype), params)

    def step(self, params, grads):
        o, f32 = self.o, np.float32
        self.count += 1
        bc1 = f32(1) - f32(o.beta1) ** f32(self.count)
        bc2 = f32(1) - f32(o.beta2) ** f32(self.count)
        mu = _map(lambda g, m: f32(o.beta1) * m.astype(f32)
                  + f32(1 - o.beta1) * g, grads, self.mu)
        nu = _map(lambda g, n: f32(o.beta2) * n.astype(f32)
                  + f32(1 - o.beta2) * g * g, grads, self.nu)
        # decoupled weight decay after the Adam term, the learning rate last
        new = _map(lambda p, m, n: p - f32(o.alpha) * (
            (m / bc1) / (np.sqrt(n / bc2) + f32(o.epsilon))
            + f32(o.weight_decay) * p), params, mu, nu)
        self.mu = _map(lambda m: m.astype(self.dtype), mu)
        self.nu = _map(lambda n: n.astype(self.dtype), nu)
        return new

    def moments(self):
        return {"mu": self.mu, "nu": self.nu}


class _SGD:
    def __init__(self, opt, params):
        self.o = opt
        self.trace = _map(np.zeros_like, params) if opt.momentum else None

    def step(self, params, grads):
        o, f32 = self.o, np.float32
        # coupled weight decay: added to the gradient before the momentum
        g = _map(lambda g, p: g + f32(o.weight_decay) * p, grads, params)
        u = g
        if o.momentum:
            self.trace = _map(lambda g, t: g + f32(o.momentum) * t,
                              g, self.trace)
            u = _map(lambda g, t: g + f32(o.momentum) * t, g,
                     self.trace) if o.nesterov else self.trace
        return _map(lambda p, u: p - f32(o.lr) * u, params, u)

    def moments(self):
        return {"trace": self.trace} if self.trace is not None else {}


def _moments_of(opt_state):
    """The live optax state's moment trees by field name."""
    found = {}

    def visit(node):
        if isinstance(node, optax.ScaleByAdamState):
            found.update(mu=node.mu, nu=node.nu)
        elif isinstance(node, optax.TraceState):
            found.update(trace=node.trace)
        elif isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    return found


@pytest.mark.parametrize("opt", OPTS)
def test_optimizer_update_matches_a_plain_reference(opt):
    m = FFModel(FFConfig(batch_size=BATCH, only_data_parallel=True,
                         mesh_shape={"data": 1}, seed=3, strategy_cache=False,
                         log_level="warning"))
    x_t = m.create_tensor([BATCH, D_IN], name="x")
    m.dense(m.dense(x_t, D_HID, name="fc"), D_OUT, use_bias=False,
            name="head")
    cm = m.compile(opt, "mean_squared_error", metrics=[])
    cm.init(seed=0)
    assert {k: set(v) for k, v in cm.params.items()} == {
        "fc": {"kernel", "bias"}, "head": {"kernel"}}
    structure = jax.tree_util.tree_structure(cm.opt_state)

    params = _map(lambda a: np.asarray(a, np.float32), cm.params)
    ref = (_Adam if isinstance(opt, AdamOptimizer) else _SGD)(opt, params)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    for _ in range(3):     # the count, and so the bias correction, advances
        x = rng.normal(size=(BATCH, D_IN)).astype(np.float32)
        t = rng.normal(size=(BATCH, D_OUT)).astype(np.float32)
        params = ref.step(params, _gradients(params, x, t))
        # the step donates what it is handed: thread what comes back
        cm.params, cm.opt_state, cm.state, loss, _ = cm.train_step(
            cm.params, cm.opt_state, cm.state, [jnp.asarray(x)],
            jnp.asarray(t), key)
        assert np.isfinite(float(loss))
        assert jax.tree_util.tree_structure(cm.opt_state) == structure

    bf16 = getattr(opt, "state_dtype", "float32") == "bfloat16"
    got = _moments_of(cm.opt_state)
    assert set(got) == set(ref.moments())
    for name, want in ref.moments().items():
        for a, b in zip(jax.tree_util.tree_leaves(got[name]),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
            # a bf16 moment may round the other way on the last f32 bit
            np.testing.assert_allclose(
                np.asarray(a, np.float32), b.astype(np.float32),
                rtol=2 ** -7 if bf16 else 1e-4, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(cm.params),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-4,
                                   atol=2e-5 if bf16 else 2e-6)


@pytest.mark.parametrize("gone", [
    "--fused-optimizer", "--fused-loss", "FFConfig(fused_loss='on')",
    "GPT2Config(vocab_pad_to=128)"])
def test_the_fused_optimizer_option_is_refused_by_name(gone):
    """The Pallas update kernel (PR 31), the fused cross-entropy kernel and
    the padded vocabulary it needed (PR 46) are gone with their options. A
    command line that still carries a flag must not pass it by as one of the
    user script's (`parse_known_args` would, and the launcher would then
    take its value for the script's path); a field that is gone is a
    TypeError by construction."""
    from flexflow_tpu.models.gpt2 import GPT2Config

    fields = {"FFConfig(fused_loss='on')": (FFConfig, "fused_loss", "on"),
              "GPT2Config(vocab_pad_to=128)": (GPT2Config, "vocab_pad_to", 128)}
    if gone in fields:
        cls, field, value = fields[gone]
        with pytest.raises(TypeError, match=field):
            cls(**{field: value})
        return
    with pytest.raises(SystemExit, match=f"{gone} is gone"):
        FFConfig.parse_args([gone, "off"])
    with pytest.raises(SystemExit, match=f"{gone} is gone"):
        FFConfig.parse_args(["-b", "8", f"{gone}=on"])
    assert gone not in FFConfig.launcher_value_flags()
    if gone == "--fused-optimizer":
        with pytest.raises(TypeError, match="fused_optimizer"):
            FFConfig(fused_optimizer="off")
    else:
        # what takes the flag's place is said in one sentence
        with pytest.raises(SystemExit, match="always the optax form"):
            FFConfig.parse_args([gone, "on"])
