"""Benchmark: GPT-2 medium training throughput on the available TPU chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Metric: samples/sec/chip training GPT-2 medium (BASELINE.md config #5).
vs_baseline is measured throughput relative to a hand-tuned reference anchor:
40% MFU (a strong expert-tuned single-chip GPT-2 training baseline) at the
chip's bf16 peak — vs_baseline >= 1.0 means we beat the expert anchor.

Needs a TPU: with no chip (or a chip that is not in
parallel/machine.CHIP_PRESETS) it exits non-zero and prints no metric.

Sanity gates (round-1 postmortem: an unsynchronized timing reported 7.4x
chip peak): the implied MFU is computed from first-principles FLOP accounting
(embedding lookups contribute zero matmul FLOPs, the lm_head is counted) and
the benchmark REFUSES to report a physically impossible number — if implied
MFU > 100% it exits non-zero instead of printing garbage. Every timed
window ends in jax.block_until_ready on (loss, params, opt state).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def _time_steps(cm, inputs, labels, iters: int, key):
    """Run `iters` chained steps and block until the last one's outputs
    (loss, params, opt state) are ready on the device."""
    import jax

    for i in range(iters):
        key = jax.random.fold_in(key, i)
        (cm.params, cm.opt_state, cm.state, loss, _) = cm.train_step(
            cm.params, cm.opt_state, cm.state, inputs, labels, key)
    jax.block_until_ready((loss, cm.params, cm.opt_state))
    return float(loss)


def _bench_model(cfg, batch, searched: bool,
                 opt_state_dtype: str = "float32"):
    """Build + train-bench GPT-2 under one strategy; returns samples/sec."""
    import jax

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.models import build_gpt2

    ff_cfg = FFConfig(batch_size=batch, compute_dtype="bfloat16",
                      only_data_parallel=not searched,
                      search_budget=32 if searched else 0)
    model = FFModel(ff_cfg)
    build_gpt2(model, cfg, batch=batch)
    cm = model.compile(AdamOptimizer(alpha=1e-4,
                                     state_dtype=opt_state_dtype),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)

    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(0, cfg.vocab, size=(batch, cfg.seq)).astype(np.int32))
    pos = jax.device_put(np.tile(np.arange(cfg.seq, dtype=np.int32), (batch, 1)))
    labels = jax.device_put(rng.integers(0, cfg.vocab, size=(batch, cfg.seq)).astype(np.int32))
    key = jax.random.PRNGKey(0)

    # warmup: compile + 2 steps
    loss = _time_steps(cm, [ids, pos], labels, 2, key)
    assert np.isfinite(float(loss)), f"non-finite loss {loss}"

    # median-of-windows with published spread (VERDICT r4: silent best-of-3
    # hid the regression-vs-variance question; the driver artifact and the
    # docs must be reconcilable from the spread alone)
    iters = 20
    windows = []
    for rep in range(5):
        t0 = time.perf_counter()
        _time_steps(cm, [ids, pos], labels, iters, jax.random.fold_in(key, rep))
        windows.append(time.perf_counter() - t0)
    med_dt = float(np.median(windows))
    spread = (iters * batch / max(windows), iters * batch / min(windows))
    return iters * batch / med_dt, med_dt / iters, spread


def _bench_workload(build_fn, inputs_fn, loss_type, batch, iters, warmup=2,
                    one_dispatch: bool = False):
    """Generic train-throughput bench, median of 3 windows, each ending in
    block_until_ready on (loss, params). Two timing regimes:

    - default: `iters` individually dispatched steps — for steps >= ~30ms,
      where dispatch overhead is negligible AND the per-step program is
      what XLA optimizes best (measured: the fori_loop variant runs BERT
      ~13% slower — loop carries inhibit some cross-step optimization).
    - one_dispatch=True: all `iters` steps inside ONE jitted fori_loop
      (CompiledModel.make_multi_step, the Legion trace-replay analog) —
      for sub-10ms steps, where per-step host dispatch otherwise
      dominates the window."""
    import jax

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel

    ff_cfg = FFConfig(batch_size=batch, compute_dtype="bfloat16",
                      only_data_parallel=True)
    model = FFModel(ff_cfg)
    out = build_fn(model)
    cm = model.compile(AdamOptimizer(alpha=1e-4), loss_type=loss_type,
                       metrics=[], outputs=[out] if out is not None else None)
    cm.init(seed=0)
    xs, labels = inputs_fn()
    key = jax.random.PRNGKey(0)
    times = []

    if one_dispatch:
        # stacked (iters, ...) batches; the repeated batch keeps memory at
        # iters x input size (activations don't stack)
        dx = [jax.device_put(np.broadcast_to(a, (iters,) + a.shape).copy())
              for a in xs]
        dy = jax.device_put(np.broadcast_to(labels, (iters,) + labels.shape)
                            .copy())
        multi = cm.make_multi_step(iters)
        p, o, s = cm.params, cm.opt_state, cm.state
        p, o, s, loss, _ = multi(p, o, s, dx, dy, key)  # compile + warm
        jax.block_until_ready((loss, p))
        for rep in range(3):
            t0 = time.perf_counter()
            p, o, s, loss, _ = multi(p, o, s, dx, dy,
                                     jax.random.fold_in(key, 100 + rep))
            jax.block_until_ready((loss, p))
            times.append(time.perf_counter() - t0)
    else:
        dx = [jax.device_put(a) for a in xs]
        dy = jax.device_put(labels)
        for i in range(warmup):
            cm.params, cm.opt_state, cm.state, loss, _ = cm.train_step(
                cm.params, cm.opt_state, cm.state, dx, dy,
                jax.random.fold_in(key, i))
        jax.block_until_ready((loss, cm.params, cm.opt_state))
        for rep in range(3):
            t0 = time.perf_counter()
            for i in range(iters):
                cm.params, cm.opt_state, cm.state, loss, _ = cm.train_step(
                    cm.params, cm.opt_state, cm.state, dx, dy,
                    jax.random.fold_in(key, 100 + rep * iters + i))
            jax.block_until_ready((loss, cm.params, cm.opt_state))
            times.append(time.perf_counter() - t0)
    lf = float(loss)
    assert np.isfinite(lf), lf
    return iters * batch / float(np.median(times))


def _bench_bert() -> float:
    """BASELINE config #3: BERT-base pretraining proxy throughput."""
    from flexflow_tpu.models import build_bert

    batch, seq, vocab = 8, 512, 30522

    def build(m):
        ins, logits = build_bert(m, batch=batch, seq=seq)
        return logits

    def inputs():
        rng = np.random.default_rng(0)
        ids = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
        pos = np.tile(np.arange(seq, dtype=np.int32), (batch, 1))
        lab = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
        return [ids, pos], lab

    return _bench_workload(build, inputs, "sparse_categorical_crossentropy",
                           batch, iters=10)


def _bench_resnext() -> float:
    """OSDI'22 AE workload: ResNeXt-50 (32x4d) training throughput
    (reference scripts/osdi22ae/resnext-50.sh)."""
    from flexflow_tpu.models import build_resnext50

    batch = 64

    def build(m):
        x, out = build_resnext50(m, batch=batch)
        return out

    def inputs():
        rng = np.random.default_rng(0)
        x = rng.normal(size=(batch, 3, 224, 224), scale=0.5).astype(np.float32)
        y = rng.integers(0, 1000, size=(batch,)).astype(np.int32)
        return [x], y

    return _bench_workload(build, inputs, "sparse_categorical_crossentropy",
                           batch, iters=10)


def _bench_dlrm() -> float:
    """BASELINE config #4: DLRM click-through throughput."""
    from flexflow_tpu.models import build_dlrm

    batch = 4096
    tables = (100_000,) * 8

    def build(m):
        ins, out = build_dlrm(m, batch=batch, embedding_tables=tables,
                              embedding_dim=64)
        return out

    def inputs():
        rng = np.random.default_rng(0)
        dense = rng.normal(size=(batch, 13)).astype(np.float32)
        sparse = [rng.integers(0, t, size=(batch, 1)).astype(np.int32)
                  for t in tables]
        lab = rng.uniform(size=(batch, 1)).astype(np.float32)
        return [dense] + sparse, lab

    # one_dispatch + 200 iters: DLRM steps are a few ms, so one fori_loop
    # dispatch of 200 steps measures the chip and not per-step host dispatch
    return _bench_workload(build, inputs, "mean_squared_error", batch,
                           iters=200, one_dispatch=True)


def _predicted_interop_search_win():
    """VERDICT r5 item 2: an artifact where the search STRICTLY beats every
    shipped expert template. Templates: (a) pure data parallel, (b) the best
    op-level-only plan (everything searched EXCEPT inter-op placement —
    i.e. the strongest strategy an intra-op expert can write). The searched
    plan places the fork-joins on disjoint device groups with owned (stacked,
    axis-sharded) branch weights; the ratio is predicted on the v5p target
    mesh by the same calibrated cost model that ranks strategies. The model
    and templates are shared with the dryrun's executable twin
    (flexflow_tpu/models/branchy.py)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.branchy import build_branchy, expert_template_pins
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.dp import search_graph

    def model():
        m = FFModel(FFConfig(batch_size=1024))
        build_branchy(m)
        return m

    mach = MachineSpec(mesh_axes={"data": 8, "model": 4}, chip="v5p")
    searched = search_graph(model(), mach)
    m_i = model()
    intra_only = search_graph(m_i, mach, pins=expert_template_pins(m_i, "intra_op"))
    m_d = model()
    pure_dp = search_graph(m_d, mach, pins=expert_template_pins(m_d, "dp"))
    best_template = min(intra_only.cost, pure_dp.cost)
    return {
        "ratio": best_template / searched.cost,
        "searched_ms": searched.cost * 1e3,
        "intra_op_expert_ms": intra_only.cost * 1e3,
        "pure_dp_ms": pure_dp.cost * 1e3,
        "strategy_diff": {
            name: cand.name for name, cand in searched.choices.items()
            if name.startswith("fj")
        },
    }


def _predicted_multichip_ratio():
    """Cost-model-predicted searched-vs-expert ratio for the v5p TARGET mesh
    (8 data x 4 model): both strategies costed by the same frontier DP,
    entirely analytic (no devices needed). This — not the 1-chip wall-clock
    number — is the meaningful multi-chip anchor the single-chip bench can
    produce; __graft_entry__.dryrun_multichip runs the CPU-mesh twin."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import GPT2Config, build_gpt2
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.dp import search_graph

    cfg = GPT2Config.medium()
    cfg.dropout = 0.0
    model = FFModel(FFConfig(batch_size=32))
    build_gpt2(model, cfg, batch=32)
    mach = MachineSpec(mesh_axes={"data": 8, "model": 4}, chip="v5p")
    searched = search_graph(model, mach).cost
    pins = {}
    for i in range(cfg.layers):
        pins[f"h{i}_attn"] = "tp_heads:model"
        pins[f"h{i}_mlp_up"] = "tp_col:model"
        pins[f"h{i}_mlp_down"] = "tp_row:model"
    expert = search_graph(model, mach, pins=pins).cost
    return expert / searched


def main():
    import jax

    from flexflow_tpu.models import GPT2Config
    from flexflow_tpu.parallel.machine import MachineSpec

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU: jax.devices()[0].platform is "
            f"{dev.platform!r}; a CPU timing is not a device metric")
    machine = MachineSpec.detect()  # raises for a chip without peaks

    # BASELINE config #5: GPT-2 medium, seq 1024
    cfg = GPT2Config.medium()
    batch = 8
    cfg.dropout = 0.0

    # expert strategy (hand-tuned data-parallel anchor) = the reported metric;
    # the auto-searched strategy on the same mesh gives BASELINE's second
    # north-star: searched_vs_expert (target >= 0.90)
    sps, step_dt, spread = _bench_model(cfg, batch, searched=False)
    searched_sps, _, _ = _bench_model(cfg, batch, searched=True)
    # opt-in reduced-precision Adam moments (AdamOptimizer state_dtype=
    # "bfloat16"): reported as a secondary number — the headline stays on
    # the quality-default fp32 moments
    bf16st_sps, _, _ = _bench_model(cfg, batch, searched=False,
                                    opt_state_dtype="bfloat16")
    # MFU-ceiling evidence: same model at head_dim 128 (heads halved,
    # identical params/FLOPs) — attention matmuls fill the MXU's 128-deep
    # contraction, clearing the head_dim-64 ~50% cap (BASELINE.md analysis)
    import dataclasses as _dc

    cfg_h128 = _dc.replace(cfg, heads=cfg.heads // 2)
    h128_sps, _, h128_spread = _bench_model(cfg_h128, batch, searched=False)
    bert_sps = _bench_bert()
    dlrm_sps = _bench_dlrm()
    resnext_sps = _bench_resnext()
    predicted_ratio = _predicted_multichip_ratio()
    interop_win = _predicted_interop_search_win()

    n_chips = max(1, len(jax.devices()))
    sps_chip = sps / n_chips

    flops_per_sample = cfg.flops_per_token() * cfg.seq
    achieved_flops = sps_chip * flops_per_sample
    mfu = achieved_flops / machine.flops
    h128_mfu = h128_sps / n_chips * flops_per_sample / machine.flops
    # the sanity gate covers EVERY reported GPT-2 throughput (headline,
    # bf16-state, h128) — any one implying >1.0 MFU means the timing or
    # FLOP accounting broke, and no number from this run can be trusted
    worst_mfu = max(mfu, h128_mfu,
                    bf16st_sps / n_chips * flops_per_sample / machine.flops)
    if worst_mfu > 1.0:
        print(json.dumps({
            "metric": "gpt2_medium_train_samples_per_sec_per_chip",
            "value": None, "unit": "samples/s/chip", "vs_baseline": None,
            "error": f"implied MFU {worst_mfu:.2f} > 1.0 is physically "
                     "impossible; refusing to report (timing or FLOP "
                     "accounting broken)",
        }), file=sys.stderr)
        raise SystemExit(1)

    # expert anchor: 40% MFU at chip bf16 peak
    ref_sps = 0.40 * machine.flops / flops_per_sample
    print(json.dumps({
        "metric": "gpt2_medium_train_samples_per_sec_per_chip",
        "value": round(sps_chip, 3),
        "unit": "samples/s/chip",
        "vs_baseline": round(sps_chip / ref_sps, 4),
        "mfu": round(mfu, 4),
        "step_ms": round(step_dt * 1e3, 2),
        # median of 5 x 20-step windows; spread = [worst, best] window
        "spread_samples_per_sec_per_chip": [round(s / n_chips, 3) for s in spread],
        # 1-chip searched-vs-expert: the mesh has ONE device, so the search
        # has nothing to shard — this checks search/jit overhead only. The
        # multi-chip anchor is the PREDICTED ratio below (cost model on the
        # v5p 8x4 target mesh) + the dryrun's executable CPU-mesh ratio.
        "bf16_opt_state_samples_per_sec_per_chip": round(bf16st_sps / n_chips, 3),
        # same params/FLOPs at head_dim 128: the framework clears the
        # head_dim-64 architectural attention cap (see BASELINE.md)
        "head_dim128_samples_per_sec_per_chip": round(h128_sps / n_chips, 3),
        "head_dim128_spread": [round(s / n_chips, 3) for s in h128_spread],
        "head_dim128_mfu": round(h128_mfu, 4),
        "searched_vs_expert": round(searched_sps / sps, 4),
        "searched_vs_expert_note": "1-chip overhead check, not a sharding anchor",
        "predicted_multichip_searched_vs_expert": round(predicted_ratio, 4),
        # the search STRICTLY beating every expert template (branchy
        # workload, inter-op placement + owned weights; see MULTICHIP for
        # the executable CPU-mesh twin of this comparison)
        "predicted_interop_searched_vs_best_expert": round(interop_win["ratio"], 4),
        "interop_searched_strategy": interop_win["strategy_diff"],
        "bert_samples_per_sec_per_chip": round(bert_sps / n_chips, 3),
        "dlrm_samples_per_sec_per_chip": round(dlrm_sps / n_chips, 3),
        "resnext50_samples_per_sec_per_chip": round(resnext_sps / n_chips, 3),
        "batch": batch,
        "seq": cfg.seq,
        "chip_peak_tflops": round(machine.flops / 1e12, 1),
        "flops_per_sample_g": round(flops_per_sample / 1e9, 1),
        "params_m": round(cfg.param_count() / 1e6, 1),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n_chips},
    }))


if __name__ == "__main__":
    main()
