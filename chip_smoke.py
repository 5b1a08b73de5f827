"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the normal entry points, GPT-2 medium at its published widths
(24 layers, d_model 1024, 16 heads, seq 1024, vocab 50257), random weights
and data made from --seed:

    python chip_smoke.py            # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4  # four chips: ONLY the multi-chip phase

  train      FFModel + build_gpt2 -> model.compile (default config: fusion
             on, fused loss "auto") -> cm.fit: a few steps of the
             async loop, loss finite and falling on a fixed seeded dataset.
  serve      the same trained graph through compile_serving +
             ContinuousBatchingScheduler.run: every request completes, and
             every served greedy token is the argmax (or a stated near-tie)
             of a plain full forward of the same parameters (cm.forward).
  multichip  (--chips 4) the searched strategy on a {data:2, model:2} mesh
             against the data-parallel template on {data:4}: same seed, same
             steps, losses agree to bf16 tolerance, every chip holds shards,
             the compiled step contains collectives.

Every earlier stdout line is one JSON object of facts; the LAST line is
{"ok": ..., "device": {"platform", "kind", "count"}}. Any failing phase
makes the exit code non-zero and "ok" false. Without a TPU (or outside the
checkout) it exits non-zero and prints no result. It touches JAX only after
the phases are chosen, starts no child process, needs no network and reads
nothing outside the checkout (the strategy cache is switched off).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

ONE_CHIP_PHASES = ("train", "serve")
MULTI_CHIP_PHASES = ("multichip",)
KERNELS = ("flash_attention", "dequant_attention")
# token parity: a served token that is not the reference argmax must be
# within this many bf16 ulps (at the logits' scale) of the reference max
NEAR_TIE_ULPS = 8.0


def emit(**facts) -> None:
    print(json.dumps(facts), flush=True)


def select_phases(chips: int):
    if chips not in (1, 4):
        raise SystemExit(f"--chips must be 1 or 4, got {chips}")
    return MULTI_CHIP_PHASES if chips == 4 else ONE_CHIP_PHASES


def require_tpu(chips: int) -> dict:
    """The one chip-only assertion. Fails (no result printed) when JAX finds
    no TPU or fewer chips than the phase needs. `count` is the chips the
    phases run on (their meshes are pinned to it), which on the machines
    this is run on is also len(jax.devices())."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU: jax.devices()[0].platform is "
            f"{devs[0].platform!r} (no accelerator found; nothing was run)")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke.py --chips {chips} needs {chips} TPU "
                         f"chips, JAX reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def kernels_in(compiled_text: str) -> dict:
    """Which of the repo's Pallas kernels the compiled program really
    contains: every pallas_call of flexflow_tpu/kernels carries a stable
    name ("ff_<kernel>..."), which lands in the op_name of its Mosaic custom
    call — a kernel counts only when its tpu_custom_call is there."""
    calls = [ln for ln in compiled_text.splitlines()
             if "tpu_custom_call" in ln]
    return {k: sum(f"/ff_{k}" in ln for ln in calls) for k in KERNELS}


def collectives_in(compiled_text: str) -> dict:
    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {op: compiled_text.count(f" {op}(") + compiled_text.count(
        f" {op}-start(") for op in ops}


class CompileCounter:
    """This process's XLA compiles, from jax.monitoring. `requests`: every
    program handed to the backend — a persistent-cache hit included, a
    program jit still holds in memory not. `hits`: read back from the
    persistent cache. `misses`: JAX's own event of that name, which fires
    when a compiled program is WRITTEN to the cache, and JAX writes only
    compiles of a second or more — so hits + misses <= requests."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, _secs: float, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def facts(self) -> dict:
        return {"compile_requests": self.requests,
                "compile_cache_hits": self.hits,
                "compile_cache_misses": self.misses}


_compiles = None


def compiles() -> CompileCounter:
    """The process's one counter (jax.monitoring listeners are global)."""
    global _compiles
    if _compiles is None:
        _compiles = CompileCounter()
    return _compiles


def dispatched_text(jitted, *args) -> str:
    """Optimized HLO of the executable `jitted` has already dispatched for
    these arguments. Lowering again with the same avals and shardings is
    answered from jit's in-memory caches (the same module, the same
    executable): nothing is compiled. That is asserted, because a program
    compiled here would be a second one and not the one that ran."""
    before = compiles().requests
    text = jitted.lower(*args).compile().as_text()
    assert compiles().requests == before, (
        "the program inspected is not the one that was dispatched: lowering "
        "it again compiled a new executable")
    return text


def peak_bytes(devices, used: bool = True) -> list:
    """peak_bytes_in_use of each device. A TPU reports it, and a chip that a
    phase `used` holds something: zero there fails the phase."""
    mem = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
           for d in devices]
    if used and devices[0].platform == "tpu":
        assert all(m > 0 for m in mem), f"a chip reports no memory use: {mem}"
    return mem


def _dataset(gcfg, n: int, seed: int):
    """A learnable next-token task from a seed: every sequence walks the
    vocab with one of 8 fixed strides, so a few Adam steps already lower
    the loss."""
    import numpy as np

    rng = np.random.default_rng(seed)
    start = rng.integers(0, gcfg.vocab, size=(n, 1))
    stride = rng.choice(np.array([1, 2, 3, 5, 7, 11, 13, 17]), size=(n, 1))
    walk = (start + stride * np.arange(gcfg.seq + 1)[None, :]) % gcfg.vocab
    ids = walk[:, :-1].astype(np.int32)
    labels = walk[:, 1:].astype(np.int32)
    pos = np.tile(np.arange(gcfg.seq, dtype=np.int32), (n, 1))
    return [ids, pos], labels


def _build(gcfg, batch: int, seed: int, init: bool = True, **cfg_kw):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.models import build_gpt2

    # default config otherwise. strategy_cache=False: nothing outside the
    # committed files may steer the run.
    cfg = FFConfig(batch_size=batch, compute_dtype="bfloat16", seed=seed,
                   strategy_cache=False, log_level="warning", **cfg_kw)
    model = FFModel(cfg)
    build_gpt2(model, gcfg, batch=batch)
    t0 = time.perf_counter()
    cm = model.compile(AdamOptimizer(alpha=3e-4),
                       loss_type="sparse_categorical_crossentropy",
                       metrics=[])
    t_search = time.perf_counter() - t0
    t0 = time.perf_counter()
    if init:
        cm.init(seed=seed)
    return model, cm, t_search, time.perf_counter() - t0


def _step_text(cm, x, y) -> str:
    """Optimized HLO of the train step fit() just ran: one more batch with
    the loader's shardings, and the parameters fit() left behind."""
    import jax

    b = cm.cfg.batch_size
    dx = [jax.device_put(a[:b], cm.input_sharding(t))
          for a, t in zip(x, cm.model.input_tensors)]
    dy = jax.device_put(y[:b], cm.label_sharding(y[:b].shape))
    return dispatched_text(cm.train_step, cm.params, cm.opt_state, cm.state,
                           dx, dy, jax.random.PRNGKey(0))


def run_train(gcfg, batch: int, seed: int, batches: int = 4, epochs: int = 3):
    """Train phase. Returns (model, cm) for the serve phase."""
    import numpy as np

    # one chip, whatever the host shows: the default mesh would be
    # {data: len(jax.devices())}
    model, cm, t_search, t_init = _build(gcfg, batch, seed,
                                         mesh_shape={"data": 1})
    x, y = _dataset(gcfg, batch * batches, seed)
    t0 = time.perf_counter()
    hist = cm.fit(x, y, epochs=epochs, verbose=False)
    t_fit = time.perf_counter() - t0
    losses = [float(h["loss"]) for h in hist]
    text = _step_text(cm, x, y)
    kernels = kernels_in(text)
    emit(phase="train", model="gpt2_medium" if gcfg.layers == 24 else "gpt2",
         layers=gcfg.layers, d_model=gcfg.d_model, heads=gcfg.heads,
         seq=gcfg.seq, vocab=gcfg.vocab, batch=batch,
         strategy=cm.strategy.name, mesh=dict(cm.machine.mesh_axes),
         steps=epochs * batches, epoch_mean_losses=losses,
         first_loss=losses[0], last_loss=losses[-1],
         search_compile_model_s=round(t_search, 3), init_s=round(t_init, 3),
         fit_s_incl_xla_compile=round(t_fit, 3),
         fit_dispatches=cm.step_stats.get("dispatches"),
         fit_host_syncs=cm.step_stats.get("host_syncs"),
         kernels_in_train_step=kernels)
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    # every kernel the config selected is in the compiled step
    assert kernels["flash_attention"] >= 3, kernels  # fwd + dq + dkv
    return model, cm


def run_serve(model, cm, gcfg, seed: int, n_requests: int = 6,
              prompt_lens=(200, 400), max_new: int = 32):
    """Serve phase over the trained graph."""
    import jax
    import numpy as np

    from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                      compile_serving, gpt2_prompt_inputs,
                                      gpt2_step_inputs)

    t0 = time.perf_counter()
    eng = compile_serving(model, max_decode_len=max_new)
    eng.load_params(cm.params)
    t_compile = time.perf_counter() - t0
    rng = np.random.default_rng(seed + 1)
    reqs = [Request(rid=i,
                    prompt=[int(t) for t in rng.integers(
                        1, gcfg.vocab, size=int(rng.integers(*prompt_lens)))],
                    max_new_tokens=max_new, arrival_s=0.0)
            for i in range(n_requests)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None)
    t0 = time.perf_counter()
    done = sched.run(reqs)
    t_run = time.perf_counter() - t0
    complete = [r for r in done if len(r.tokens) == r.max_new_tokens]

    # reference: ONE plain full forward of the same parameters over
    # prompt + served tokens (teacher-forced), row r = request r
    b = cm.cfg.batch_size
    assert n_requests <= b
    ids = np.zeros((b, gcfg.seq), np.int32)
    for r in done:
        seq = r.prompt + r.tokens
        ids[r.rid, :len(seq)] = seq
    pos = np.tile(np.arange(gcfg.seq, dtype=np.int32), (b, 1))
    ref = np.asarray(cm.forward(ids, pos).astype("float32"))
    ref = ref[..., :gcfg.vocab]
    equal = n_tok = 0
    worst = 0.0
    for r in done:
        p = len(r.prompt)
        rows = ref[r.rid, p - 1:p - 1 + len(r.tokens)]
        top = rows.max(axis=-1)
        got = rows[np.arange(len(r.tokens)), np.asarray(r.tokens)]
        scale = np.maximum(1.0, np.abs(rows).max(axis=-1))
        gap = (top - got) / (scale * 2.0 ** -8)   # in bf16 ulps
        equal += int((gap == 0).sum())
        n_tok += len(r.tokens)
        worst = max(worst, float(gap.max()))
    dec_state = eng.kv.state
    step_in = gpt2_step_inputs(jax.numpy.zeros((eng.slots, 1), "int32"),
                               dec_state)
    decode_text = dispatched_text(eng._decode_jit, eng.params, dec_state,
                                  list(step_in))
    # the program the scheduler dispatched: the one that takes the first
    # tokens on the device (the full-logits jit compiled nothing)
    assert eng._prefill_jit._cache_size() == 0
    no_lengths = np.zeros((eng.slots,), np.int32)
    prefill_text = dispatched_text(
        eng._prefill_first_tokens_jit, eng.params,
        [jax.numpy.asarray(a) for a in gpt2_prompt_inputs(
            np.zeros((eng.slots, gcfg.seq), np.int32), no_lengths)],
        jax.numpy.asarray(no_lengths))
    emit(phase="serve", requests=n_requests, completed=len(complete),
         shed=len(sched.shed), failed=len(sched.failed),
         prompt_tokens=[len(r.prompt) for r in reqs], max_new_tokens=max_new,
         slots=eng.slots, kv_dtype=str(eng.kv_dtype),
         decode_steps=sched.decode_steps, prefill_batches=sched.prefills,
         compile_serving_s=round(t_compile, 3),
         run_s_incl_xla_compile=round(t_run, 3),
         parity_tokens=n_tok, parity_tokens_equal_argmax=equal,
         parity_worst_gap_bf16_ulps=round(worst, 3),
         parity_tolerance_bf16_ulps=NEAR_TIE_ULPS,
         parity_ok=bool(worst <= NEAR_TIE_ULPS),
         kernels_in_prefill=kernels_in(prefill_text),
         kernels_in_decode_step=kernels_in(decode_text),
         decode_note="bf16 KV cache (kv_cache_dtype auto): decode attention "
                     "is the einsum path; the dequant kernel is selected "
                     "only by --kv-cache-dtype int8")
    assert len(complete) == n_requests, (len(complete), sched.shed,
                                         sched.failed)
    assert n_tok == n_requests * max_new
    assert worst <= NEAR_TIE_ULPS, f"served token {worst} bf16 ulps off argmax"
    assert kernels_in(prefill_text)["flash_attention"] >= 1


def run_multichip(gcfg, batch: int, seed: int, steps: int = 3,
                  rtol: float = 2e-2):
    """Searched {data:2, model:2} vs the data-parallel template {data:4}."""
    import gc

    import jax
    import numpy as np

    x, y = _dataset(gcfg, batch, seed)   # one batch, `steps` epochs of it
    legs = {}
    for name, kw in (
            ("searched", dict(mesh_shape={"data": 2, "model": 2},
                              search_budget=32)),
            ("data_parallel", dict(mesh_shape={"data": 4},
                                   only_data_parallel=True))):
        model, cm, t_search, t_init = _build(gcfg, batch, seed, **kw)
        t0 = time.perf_counter()
        hist = cm.fit(x, y, epochs=steps, verbose=False)
        t_fit = time.perf_counter() - t0
        losses = [float(h["loss"]) for h in hist]
        # a weight the strategy shards, else any weight: how many chips
        # hold a piece of it
        leaves = jax.tree_util.tree_leaves(cm.params)
        sharded = [l for l in leaves if not l.sharding.is_fully_replicated]
        probe = sharded[0] if sharded else leaves[0]
        held_by = len({s.device for s in probe.addressable_shards})
        mem = peak_bytes(list(cm.mesh.devices.flat))
        text = _step_text(cm, x, y)
        legs[name] = dict(losses=losses, held_by=held_by, mem=mem,
                          collectives=collectives_in(text),
                          sharded_weights=len(sharded))
        emit(phase="multichip", leg=name, strategy=cm.strategy.name,
             mesh=dict(cm.machine.mesh_axes),
             mesh_device_ids=np.vectorize(lambda d: d.id)(
                 cm.mesh.devices).tolist(),
             losses=losses, sharded_weights=len(sharded),
             probe_weight_shape=list(probe.shape),
             probe_weight_on_devices=held_by,
             peak_bytes_in_use_per_device=mem,
             collectives=legs[name]["collectives"],
             kernels_in_train_step=kernels_in(text),
             search_compile_model_s=round(t_search, 3),
             init_s=round(t_init, 3), fit_s_incl_xla_compile=round(t_fit, 3))
        assert all(np.isfinite(losses)), losses
        assert held_by == 4, f"{name}: probe weight lives on {held_by} chips"
        assert sum(legs[name]["collectives"].values()) > 0, \
            f"{name}: no collective in the compiled step"
        del model, cm, hist, leaves, sharded, probe
        gc.collect()
    a, b = legs["searched"]["losses"], legs["data_parallel"]["losses"]
    err = max(abs(p - q) / max(abs(q), 1e-6) for p, q in zip(a, b))
    emit(phase="multichip", leg="compare", max_rel_loss_diff=err, rtol=rtol,
         searched_shards_weights=legs["searched"]["sharded_weights"] > 0)
    assert err <= rtol, f"searched vs data-parallel losses differ: {a} vs {b}"
    assert legs["searched"]["sharded_weights"] > 0, \
        "the searched strategy sharded no weight on the 2x2 mesh"


def run_phases(phases, gcfg, batch: int, seed: int) -> bool:
    """Runs the selected phases in order; False as soon as one fails (the
    failure is printed as a JSON line, later phases are skipped)."""
    ctx = {}
    for phase in phases:
        try:
            if phase == "train":
                ctx["model"], ctx["cm"] = run_train(gcfg, batch, seed)
            elif phase == "serve":
                run_serve(ctx["model"], ctx["cm"], gcfg, seed)
            elif phase == "multichip":
                run_multichip(gcfg, batch, seed)
            else:
                raise ValueError(f"unknown phase {phase!r}")
        except Exception as e:  # reported, and the run FAILS (never exit 0)
            import traceback

            traceback.print_exc()
            emit(phase=phase, ok=False, error=f"{type(e).__name__}: {e}"[:2000])
            return False
    return True


def gpt2_medium():
    from flexflow_tpu.models import GPT2Config

    gcfg = GPT2Config.medium()
    # a hyper-parameter, not a width: with the default 0.1 the attention
    # takes the einsum path in training (the flash kernel has no dropout)
    gcfg.dropout = 0.0
    return gcfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1 (default): train + serve on one chip; 4: only "
                         "the multi-chip phase on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    phases = select_phases(args.chips)        # decided before JAX is touched
    try:
        import flexflow_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke.py runs from the root of a checkout "
                         f"of the repository: {e}")
    t0 = time.perf_counter()
    device = require_tpu(args.chips)

    import jax
    import jaxlib

    from flexflow_tpu.config import ensure_compile_cache

    compiles()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string only
        libtpu = "unknown"
    emit(phase="setup", phases=list(phases), jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu, device=device,
         visible_devices=len(jax.devices()),
         compile_cache_dir=ensure_compile_cache(),
         strategy_cache="off (strategy_cache=False): nothing under ~/.cache "
                        "or .ff_cache is read", seed=args.seed)
    ok = run_phases(phases, gpt2_medium(), batch=8, seed=args.seed)
    try:
        peak = peak_bytes(jax.devices()[:args.chips], used=ok)
    except AssertionError as e:
        ok, peak = False, str(e)
    emit(phase="summary", wall_s=round(time.perf_counter() - t0, 3),
         peak_bytes_in_use_per_device=peak, **compiles().facts())
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
