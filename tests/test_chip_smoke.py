"""chip_smoke.py's helpers at a tiny width on the CPU mesh, and the rules the
bring-up PR set: phase selection, a failing phase fails the run, the
compile-cache rule, no default chip, no silent kernel fallback.

What only a chip can show (platform == "tpu", Mosaic custom calls in the
compiled text) is what these tests monkeypatch — never a program option."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _tiny():
    from flexflow_tpu.models import GPT2Config

    return GPT2Config(vocab=512, seq=128, d_model=64, heads=2, layers=1,
                      dropout=0.0)


@pytest.fixture
def on_fake_chip(monkeypatch):
    """main() believes it found a chip and builds a tiny GPT-2."""
    monkeypatch.setattr(
        cs, "require_tpu", lambda chips: dict(FAKE_DEVICE, count=chips))
    monkeypatch.setattr(cs, "gpt2_medium", _tiny)


def _last_json(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return [json.loads(ln) for ln in lines]


# ------------------------------------------------------------ phase selection
def test_phase_selection():
    assert cs.select_phases(1) == ("train", "serve")
    assert cs.select_phases(4) == ("multichip",)
    with pytest.raises(SystemExit):
        cs.select_phases(2)


@pytest.mark.parametrize("chips,expect", [(1, ["train", "serve"]),
                                          (4, ["multichip"])])
def test_main_runs_only_the_selected_phases(on_fake_chip, monkeypatch, capsys,
                                            chips, expect):
    ran = []
    monkeypatch.setattr(cs, "run_train",
                        lambda *a, **k: ran.append("train") or (None, None))
    monkeypatch.setattr(cs, "run_serve", lambda *a, **k: ran.append("serve"))
    monkeypatch.setattr(cs, "run_multichip",
                        lambda *a, **k: ran.append("multichip"))
    assert cs.main(["--chips", str(chips)]) == 0
    assert ran == expect
    out = _last_json(capsys)
    assert out[-1] == {"ok": True, "device": dict(FAKE_DEVICE, count=chips)}
    assert out[0]["phase"] == "setup" and out[0]["phases"] == expect


def test_failing_phase_fails_the_run(on_fake_chip, monkeypatch, capsys):
    def boom(*a, **k):
        raise AssertionError("loss did not fall")

    ran = []
    monkeypatch.setattr(cs, "run_train", boom)
    monkeypatch.setattr(cs, "run_serve", lambda *a, **k: ran.append("serve"))
    assert cs.main([]) == 1
    out = _last_json(capsys)
    assert out[-1]["ok"] is False and out[-1]["device"] == FAKE_DEVICE
    assert any(o.get("phase") == "train" and o.get("ok") is False
               and "loss did not fall" in o["error"] for o in out)
    assert ran == []  # later phases are skipped, not run on a broken state


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None) and "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


# --------------------------------------------------- the phases, tiny, on CPU
def _chip_only_kernels(text):
    # interpret mode lowers the kernels to plain ops: which Mosaic custom
    # calls the compiled text holds is a fact only the chip run can show
    return {"flash_attention": 3, "dequant_attention": 0}


def test_train_and_serve_phases_tiny(devices, monkeypatch, capsys):
    monkeypatch.setattr(cs, "kernels_in", _chip_only_kernels)
    gcfg = _tiny()
    model, cm = cs.run_train(gcfg, batch=8, seed=0, batches=2, epochs=2)
    cs.run_serve(model, cm, gcfg, seed=0, n_requests=3,
                 prompt_lens=(10, 40), max_new=4)
    train, serve = _last_json(capsys)
    assert train["phase"] == "train" and train["steps"] == 4
    # one chip whatever the host shows (8 virtual devices here)
    assert train["mesh"] == {"data": 1}
    assert np.isfinite(train["last_loss"])
    assert train["last_loss"] < train["first_loss"]
    assert train["fit_host_syncs"] == 0          # the PR-2 async loop
    assert serve["completed"] == 3 and serve["parity_ok"] is True
    assert serve["parity_tokens"] == 12


def test_multichip_phase_tiny(devices, capsys):
    cs.run_multichip(_tiny(), batch=8, seed=0, steps=2)
    searched, dp, cmp_ = _last_json(capsys)
    assert searched["mesh"] == {"data": 2, "model": 2}
    assert dp["mesh"] == {"data": 4} and dp["strategy"] == "data_parallel"
    assert searched["probe_weight_on_devices"] == 4
    assert sum(searched["collectives"].values()) > 0
    assert cmp_["max_rel_loss_diff"] <= cmp_["rtol"]


def test_kernels_and_collectives_are_read_from_the_compiled_text():
    text = "\n".join([
        '%a = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/h0_attn/ff_flash_attention_fwd/pallas_call"}',
        '%b = f32[8] custom-call(%y), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/ff_dequant_attention/pallas_call"}',
        '%c = f32[8] fusion(%z), metadata={op_name="ff_dequant_attention"}',
        "%d = f32[8] all-reduce(%c), replica_groups={}",
        "%e = f32[8] all-gather-start(%d)",
    ])
    # %c names the kernel but is no Mosaic call: it does not count
    assert cs.kernels_in(text) == {"flash_attention": 1,
                                   "dequant_attention": 1}
    got = cs.collectives_in(text)
    assert got["all-reduce"] == 1 and got["all-gather"] == 1


# ------------------------------------------------------ the compile-cache rule
def test_compile_cache_env_var_wins_and_code_sets_nothing(monkeypatch):
    import jax

    from flexflow_tpu import config

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(config, "_compile_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert config.ensure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    import jax

    from flexflow_tpu import config

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(config, "_compile_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = os.path.join(ROOT, ".jax_cache")
        assert config.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert config.ensure_compile_cache() == want  # stable: never moves
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # the suite itself must never populate it (conftest turns the cache off)
    assert jax.config.jax_enable_compilation_cache is False


def test_inspected_text_must_be_the_dispatched_executable(devices):
    """dispatched_text reads the program jit already ran; a lowering that
    has to compile (here: a shape never dispatched) is refused."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a * 2 + 1)
    x = jnp.ones((8, 4))
    f(x)
    assert "multiply" in cs.dispatched_text(f, x)
    with pytest.raises(AssertionError, match="not the one that was dispatched"):
        cs.dispatched_text(f, jnp.ones((16, 4)))


# --------------------------------------------------------- no default chip
class _FakeChip:
    platform = "tpu"
    device_kind = "TPU v9 mystery"

    def __init__(self, peak=None):
        self._peak = peak

    def memory_stats(self):
        return None if self._peak is None else {"peak_bytes_in_use": self._peak}


def test_every_tpu_chip_must_report_memory_in_use(devices):
    assert cs.peak_bytes([_FakeChip(3), _FakeChip(5)]) == [3, 5]
    with pytest.raises(AssertionError, match="no memory use"):
        cs.peak_bytes([_FakeChip(3), _FakeChip(0)])
    with pytest.raises(AssertionError, match="no memory use"):
        cs.peak_bytes([_FakeChip(), _FakeChip()])   # empty stats everywhere
    cs.peak_bytes(list(devices))   # the CPU reports none: not a failure


def test_count_is_the_chips_asked_for(monkeypatch):
    """A host that shows four chips: the one-chip run says count 1 (its
    meshes are pinned to one device), --chips 4 says 4."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeChip()] * 4)
    assert cs.require_tpu(1)["count"] == 1
    assert cs.require_tpu(4)["count"] == 4
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeChip()])
    with pytest.raises(SystemExit, match="needs 4 TPU chips"):
        cs.require_tpu(4)


def test_detect_raises_on_a_chip_without_peaks(monkeypatch):
    from flexflow_tpu.parallel import machine

    monkeypatch.setattr(machine.jax, "devices", lambda: [_FakeChip()])
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        machine.MachineSpec.detect()
    with pytest.raises(ValueError, match="unknown chip"):
        machine.MachineSpec(mesh_axes={"data": 1}, chip="v9")
    assert machine.chip_for_device_kind("TPU v5 lite") == "v5e"


# ------------------------------------------------- no silent kernel fallback
def test_auto_attention_propagates_a_failing_kernel(devices, monkeypatch):
    """impl="auto" picks the flash kernel from the shape before tracing; a
    kernel that was chosen and then raises (on the chip: a Mosaic refusal)
    must fail the program, not become the einsum path."""
    import importlib

    from flexflow_tpu import FFConfig, FFModel

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

    def build():
        m = FFModel(FFConfig(batch_size=8, only_data_parallel=True,
                             log_level="warning"))
        x = m.create_tensor([8, 128, 64], name="x")
        m.multihead_attention(x, x, x, 64, 2, causal=True, name="attn")
        cm = m.compile(loss_type="mean_squared_error", metrics=[])
        cm.init(seed=0)
        return cm

    x = np.zeros((8, 128, 64), np.float32)
    assert np.isfinite(np.asarray(build().forward(x))).all()  # kernel path

    def refused(*a, **k):
        raise RuntimeError("Mosaic refused this kernel")

    monkeypatch.setattr(fa, "flash_attention_qkv", refused)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        build().forward(x)
