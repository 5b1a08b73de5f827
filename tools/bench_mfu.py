"""MFU ceiling bench: searched rematerialization + the pallas fusion suite.

Evidence harness for the ISSUE-12 tentpole, in five legs:

  remat_search — the frontier DP with per-layer remat policies under a
      tight HBM cap: reports the chosen per-layer assignment (must be
      MIXED, not all-or-nothing), the predicted memory reduction vs the
      capped no-remat search, and the recompute overhead — asserted to
      stay within the cost model's own remat_recompute_time estimate.
  remat_live — the --remat lowering (per-layer jax.checkpoint) measured
      on the COMPILED train step via XLA's memory analysis: live temp
      buffer bytes must actually shrink, and the loss stays bit-identical
      (recompute replays the same ops, including guid-folded dropout).
  fused_ce — fused cross-entropy vs the optax reference: fwd/grad
      parity, and the no-f32-[N,vocab]-materialization claim counted on
      the traced jaxpr (reference > 0, fused == 0).
  fused_optim — the single-pass Adam/SGD kernel vs tx.update across
      every recognized plan (adam / adamw / adam-bf16 / sgd / sgd-mom).
  collective_matmul — the ring all-gather/matmul overlap vs plain
      x @ w on the 8-virtual-device mesh: fwd/grad parity.

plus an op_attribution() pass over the gpt2 CPU twin with the fusion
suite off vs on — the roofline/MFU rows land in BENCH_mfu.json so the
fused kernels' movement is inspectable per op (timings on the CPU
interpret backend are structural evidence, not TPU speedups).

  python tools/bench_mfu.py                 # full run, prints JSON
  python tools/bench_mfu.py --out BENCH_mfu.json
  python tools/bench_mfu.py --check         # CI smoke: asserts every
      leg's contract (mixed per-layer remat, predicted AND live memory
      reduction, recompute overhead within the cost-model estimate,
      <= 1e-5 kernel parity on every leg) — exits nonzero on regression.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _guid_reset():
    """Pin the layer/tensor guid counters: consecutive builds otherwise
    shift every dropout stream (rng folds in the guid), breaking
    bit-identical comparisons."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.tensor import Tensor

    Layer._next_guid[0] = 100
    Tensor._next_guid[0] = 1000


def _chain_model(cfg, batch, hidden, layers):
    from flexflow_tpu import FFModel

    m = FFModel(cfg)
    x = m.create_tensor([batch, hidden], name="x")
    h = x
    for i in range(layers):
        h = m.dense(h, hidden, activation="gelu", name=f"blk{i}")
    m.dense(h, 64, name="head")
    return m


# ------------------------------------------------------------ leg 1: search
def leg_remat_search() -> dict:
    """DP-level: under a 0.4x cap the search assigns remat to SOME layers,
    buys predicted HBM with recompute priced by the cost model."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search import cost_model as cm
    from flexflow_tpu.search.dp import _score, search_graph

    mach = MachineSpec(mesh_axes={"data": 2, "model": 4}, chip="v5e")

    def build():
        from flexflow_tpu import FFConfig
        return _chain_model(FFConfig(batch_size=8192), 8192, 2048, 6)

    base = search_graph(build(), mach, beam_width=64)
    cap = base.mem_bytes * 0.4
    r = search_graph(build(), mach, beam_width=64, mem_budget=cap,
                     remat_policies=("dots", "full"))
    r0 = search_graph(build(), mach, beam_width=64, mem_budget=cap)
    model = build()
    layers = {l.name: l for l in model.layers}
    est = sum(cm.remat_recompute_time(r.choices[n].op_time(layers[n], mach),
                                      pol) for n, pol in r.remat.items())
    overhead = r.cost - r0.cost
    return {
        "hbm_cap_bytes": cap,
        "remat_assignment": dict(r.remat),
        "n_layers": len(model.layers),
        "pred_mem_no_remat_bytes": int(r0.mem_bytes),
        "pred_mem_remat_bytes": int(r.mem_bytes),
        "pred_mem_reduction": 1.0 - r.mem_bytes / r0.mem_bytes,
        "recompute_overhead_s": overhead,
        "cost_model_overhead_estimate_s": est,
        "overhead_within_estimate": bool(overhead <= est * 1.001 + 1e-12),
        "score_improves": bool(
            _score(r.cost, r.mem_bytes, cap) <
            _score(r0.cost, r0.mem_bytes, cap)),
    }


# -------------------------------------------------------------- leg 2: live
def leg_remat_live(batch=1024, hidden=256, layers=8) -> dict:
    """Compiled-artifact level: per-layer jax.checkpoint must shrink the
    train step's live temp buffers (XLA memory analysis) at bit-identical
    loss."""
    import jax

    from flexflow_tpu import FFConfig, SGDOptimizer
    from flexflow_tpu.losses import LossType

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(2 * batch, hidden)).astype(np.float32)
    ys = rng.integers(0, 64, size=(2 * batch,)).astype(np.int32)
    out = {}
    for key, remat in (("base", False), ("remat", True)):
        _guid_reset()
        cfg = FFConfig(batch_size=batch, only_data_parallel=True,
                       remat=remat, seed=3, log_level="warning")
        m = _chain_model(cfg, batch, hidden, layers)
        cmod = m.compile(SGDOptimizer(lr=0.01),
                         LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                         metrics=[])
        cmod.init(seed=0)
        lowered = cmod.train_step.lower(
            cmod.params, cmod.opt_state, cmod.state,
            [jax.device_put(xs[:batch])], jax.device_put(ys[:batch]),
            jax.random.PRNGKey(0))
        ma = lowered.compile().memory_analysis()
        hist = cmod.fit([xs], ys, epochs=1, verbose=False)
        out[key] = {"temp_bytes": int(ma.temp_size_in_bytes),
                    "loss": float(hist[0]["loss"])}
    return {
        "live_temp_base_bytes": out["base"]["temp_bytes"],
        "live_temp_remat_bytes": out["remat"]["temp_bytes"],
        "live_temp_reduction": 1.0 - out["remat"]["temp_bytes"]
        / out["base"]["temp_bytes"],
        "loss_base": out["base"]["loss"],
        "loss_remat": out["remat"]["loss"],
        "loss_bit_identical": out["base"]["loss"] == out["remat"]["loss"],
    }


# --------------------------------------------------------------- leg 3: CE
def leg_fused_ce(n=256, v=2048) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from flexflow_tpu.kernels.fused_ce import fused_cross_entropy

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(n, v)) * 3.0, jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)

    def ref(x):
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            x.astype(jnp.float32), labels))

    def fused(x):
        return fused_cross_entropy(x, labels)

    fwd_diff = abs(float(fused(logits)) - float(ref(logits)))
    gf = jax.grad(fused)(logits).astype(jnp.float32)
    gr = jax.grad(ref)(logits).astype(jnp.float32)
    grad_diff = float(jnp.max(jnp.abs(gf - gr)))

    def count_f32_nv(fn):
        jaxpr = jax.make_jaxpr(lambda x: jax.grad(fn)(x))(logits)
        cnt = 0

        def walk(jp):
            nonlocal cnt
            for eqn in jp.eqns:
                for var in eqn.outvars:
                    aval = getattr(var, "aval", None)
                    if aval is not None and tuple(aval.shape) == (n, v) \
                            and aval.dtype == jnp.float32:
                        cnt += 1
                for val in eqn.params.values():
                    if getattr(val, "jaxpr", None) is not None:
                        walk(val.jaxpr)
        walk(jaxpr.jaxpr)
        return cnt

    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(jax.grad(fused))(logits))
    t_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(jax.grad(ref))(logits))
    t_ref = time.perf_counter() - t0
    return {
        "rows": n, "vocab": v,
        "fwd_max_diff": fwd_diff,
        "grad_max_diff": grad_diff,
        "f32_nv_intermediates_ref": count_f32_nv(ref),
        "f32_nv_intermediates_fused": count_f32_nv(fused),
        "compile_plus_step_s_fused": t_fused,
        "compile_plus_step_s_ref": t_ref,
    }


# ------------------------------------------------------------ leg 4: optim
def leg_fused_optim() -> dict:
    import jax
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, SGDOptimizer
    from flexflow_tpu.kernels.fused_optim import fused_update, plan_for

    rng = np.random.default_rng(0)
    params = {"k": jnp.asarray(rng.normal(size=(33, 65)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    plans = {
        "adam": AdamOptimizer(alpha=1e-3),
        "adamw": AdamOptimizer(alpha=1e-3, weight_decay=0.01),
        "adam_bf16": AdamOptimizer(alpha=1e-3, state_dtype="bfloat16"),
        "sgd": SGDOptimizer(lr=0.05),
        "sgd_momentum": SGDOptimizer(lr=0.05, momentum=0.9, nesterov=True),
    }
    diffs = {}
    for name, opt in plans.items():
        tx = opt.to_optax()
        state = tx.init(params)
        plan = plan_for(opt)
        worst = 0.0
        ref_state = fused_state = state
        for step in range(2):
            grads = jax.tree_util.tree_map(
                lambda p: jnp.asarray(
                    np.random.default_rng(step + p.size).normal(
                        size=p.shape), jnp.float32), params)
            ref_upd, ref_state = tx.update(grads, ref_state, params)
            upd, fused_state = fused_update(plan, grads, fused_state,
                                            params)
            for a, b in zip(jax.tree_util.tree_leaves((upd, fused_state)),
                            jax.tree_util.tree_leaves((ref_upd,
                                                       ref_state))):
                worst = max(worst, float(jnp.max(jnp.abs(
                    jnp.asarray(a, jnp.float32)
                    - jnp.asarray(b, jnp.float32)))))
        diffs[name] = worst
    return {"per_plan_max_diff": diffs,
            "max_diff": max(diffs.values())}


# ------------------------------------------------------- leg 5: collective
def leg_collective_matmul(m_rows=64, k=32, n_cols=64) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from flexflow_tpu.kernels.collective_matmul import collective_matmul

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m_rows, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n_cols)), jnp.float32)
    y = collective_matmul(x, w, mesh, "model")
    ref = jnp.dot(x, w, preferred_element_type=jnp.float32)
    fwd = float(jnp.max(jnp.abs(y - ref)))

    def f_ring(x, w):
        return jnp.sum(collective_matmul(x, w, mesh, "model") ** 2)

    def f_ref(x, w):
        return jnp.sum(jnp.dot(x, w,
                               preferred_element_type=jnp.float32) ** 2)

    g = jax.grad(f_ring, argnums=(0, 1))(x, w)
    gr = jax.grad(f_ref, argnums=(0, 1))(x, w)
    grad = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(g, gr))
    return {"fwd_max_diff": fwd, "grad_max_diff": grad}


# ------------------------------------------------- op_attribution evidence
def _twin(fused: bool, batch=8):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.losses import LossType
    from flexflow_tpu.models import GPT2Config, build_gpt2

    _guid_reset()
    mode = "on" if fused else "off"
    cfg = FFConfig(batch_size=batch, only_data_parallel=True, seed=3,
                   fused_loss=mode, fused_optimizer=mode,
                   log_level="warning")
    gc = GPT2Config(vocab=512, seq=16, d_model=64, heads=2, layers=1,
                    dropout=0.0)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=batch)
    cm = m.compile(AdamOptimizer(alpha=1e-3),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    n = 16 * batch
    ids = rng.integers(0, gc.vocab, size=(n, gc.seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(gc.seq, dtype=np.int32),
                          (n, gc.seq)).copy()
    y = rng.integers(0, gc.vocab, size=(n, gc.seq)).astype(np.int32)
    return cm, [ids, pos], y


def leg_attribution(epochs=2) -> dict:
    """gpt2 twin with the fusion suite off vs on: per-op roofline/MFU
    rows + measured step time + live temp bytes (the hbm_peak proxy) —
    the movement of each row under fusion is the BENCH artifact."""
    import jax

    out = {}
    for key, fused in (("baseline", False), ("fused", True)):
        cm, x, y = _twin(fused)
        hist = cm.fit(x, y, epochs=epochs, verbose=False)
        rep = cm.op_attribution(print_table=False)
        rows = [{k: r.get(k) for k in ("layer", "op", "measured_s",
                                       "attributed_s", "roofline_s",
                                       "bound", "mfu", "mfu_ceiling")}
                for r in rep["rows"]]
        lowered = cm.train_step.lower(
            cm.params, cm.opt_state, cm.state,
            [jax.device_put(v[:cm.cfg.batch_size]) for v in x],
            jax.device_put(y[:cm.cfg.batch_size]), jax.random.PRNGKey(0))
        ma = lowered.compile().memory_analysis()
        att = sum(r["attributed_s"] or 0.0 for r in rows)
        mfu_w = (sum((r["attributed_s"] or 0.0) * (r["mfu"] or 0.0)
                     for r in rows) / att) if att > 0 else 0.0
        step = cm.drift_stats().get("measured_step_time_s")
        out[key] = {
            "rows": rows,
            "n_rows": len(rows),
            "step_ms": (step or 0.0) * 1e3,
            "mfu_weighted": mfu_w,
            "hbm_temp_bytes": int(ma.temp_size_in_bytes),
            "final_loss": float(hist[-1]["loss"]),
        }
    out["loss_max_diff"] = abs(out["baseline"]["final_loss"]
                               - out["fused"]["final_loss"])
    return out


# ------------------------------------------------------------------- driver
def run(check: bool = False) -> dict:
    t0 = time.perf_counter()
    rs = leg_remat_search()
    rl = leg_remat_live()
    ce = leg_fused_ce()
    fo = leg_fused_optim()
    cmm = leg_collective_matmul()
    att = leg_attribution()

    legs_passed = 0
    failures = []

    def leg(name, ok):
        nonlocal legs_passed
        if ok:
            legs_passed += 1
        else:
            failures.append(name)

    # per-layer, not all-or-nothing, under the cap — with priced recompute
    leg("remat_search",
        0 < len(rs["remat_assignment"]) < rs["n_layers"]
        and rs["pred_mem_reduction"] > 0
        and rs["overhead_within_estimate"] and rs["score_improves"])
    leg("remat_live",
        rl["live_temp_reduction"] > 0 and rl["loss_bit_identical"])
    leg("fused_ce",
        ce["fwd_max_diff"] <= 1e-5 and ce["grad_max_diff"] <= 1e-4
        and ce["f32_nv_intermediates_fused"] == 0
        and ce["f32_nv_intermediates_ref"] > 0)
    leg("fused_optim", fo["max_diff"] <= 1e-5)
    leg("collective_matmul",
        cmm["fwd_max_diff"] <= 1e-4 and cmm["grad_max_diff"] <= 1e-3)
    leg("attribution",
        att["baseline"]["n_rows"] > 0
        and att["baseline"]["n_rows"] == att["fused"]["n_rows"]
        and att["loss_max_diff"] <= 1e-5)

    result = {
        "remat_search": rs,
        "remat_live": rl,
        "fused_ce": ce,
        "fused_optim": fo,
        "collective_matmul": cmm,
        "op_attribution": att,
        # headline metrics (tools/bench_history.py "mfu" family)
        "remat_pred_mem_reduction": rs["pred_mem_reduction"],
        "remat_live_temp_reduction": rl["live_temp_reduction"],
        "fused_ce_max_diff": max(ce["fwd_max_diff"], ce["grad_max_diff"]),
        "step_ms_fused": att["fused"]["step_ms"],
        "mfu_weighted_fused": att["fused"]["mfu_weighted"],
        "hbm_peak_bytes": float(att["fused"]["hbm_temp_bytes"]),
        "legs_passed": legs_passed,
        "wall_s": time.perf_counter() - t0,
    }
    if failures:
        result["failures"] = failures
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        "bench_mfu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--out", default=None,
                    help="write the report JSON here (e.g. BENCH_mfu.json)")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: assert every leg's contract, write "
                         "nothing, exit nonzero on regression")
    args = ap.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_mfu] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    result = run(check=args.check)
    if args.check:
        if result.get("failures"):
            print(f"bench_mfu --check FAILED: {result['failures']}\n"
                  + json.dumps(result, indent=1, default=str))
            return 1
        print(f"bench_mfu --check OK (6/6 legs: remat "
              f"{result['remat_search']['remat_assignment']}, pred mem "
              f"-{result['remat_pred_mem_reduction']:.1%}, live temp "
              f"-{result['remat_live_temp_reduction']:.1%}, fused-ce diff "
              f"{result['fused_ce_max_diff']:.2g}, "
              f"{result['op_attribution']['baseline']['n_rows']} attr rows)")
        return 0
    print(json.dumps(result, indent=1, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, default=str)
        print(f"wrote {args.out}", file=sys.stderr)
    return 0 if not result.get("failures") else 1


if __name__ == "__main__":
    sys.exit(main())
