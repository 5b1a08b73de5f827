"""Training cells: FFModel + the family's build -> model.compile ->
cm.init(seed) -> cm.fit, repeated fit calls of a whole number of steps until
the window is full. Traffic parameters: global_batch, steps_per_fit, traced_steps. System settings:
ffconfig (FFConfig fields besides batch, seed and strategy cache), adam_lr,
loss_tolerance."""

from __future__ import annotations

import math
import statistics
import time

from cells.common import Ctx, no_compile_in_window
from families import family_of
from harness import flops, stats, traffic
from harness.facts import emit, peak_bytes


def _counters() -> dict:
    return {"steps": 0, "fit_seconds": [], "fit_losses": [],
            "fit_dispatches": 0, "fit_host_syncs": 0, "fit_barriers": 0}


def _fit_once(cm, x, y, steps, facts):
    """One fit call of `steps` steps; its counters go into `facts`."""
    t0 = time.perf_counter()
    hist = cm.fit(x, y, epochs=1, verbose=False)
    dt = time.perf_counter() - t0
    facts["fit_seconds"].append(dt)
    facts["fit_losses"].append(float(hist[-1]["loss"]))
    facts["steps"] += steps
    # the program's own counters, as they are: mid-epoch materializations
    # and queue-depth barriers (the epoch-end one is not counted by it)
    facts["fit_dispatches"] += cm.step_stats.get("dispatches", 0)
    facts["fit_host_syncs"] += cm.step_stats.get("host_syncs", 0)
    facts["fit_barriers"] += cm.step_stats.get("barriers", 0)


def run(ctx: Ctx) -> dict:
    import jax

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel

    cell, tr, sysm = ctx.cell, ctx.cell.traffic, ctx.cell.system
    family = family_of(cell.config)
    batch, steps_per_fit = int(tr["global_batch"]), int(tr["steps_per_fit"])
    facts = dict(_counters(), batch=batch)

    with ctx.span("setup/search"):
        # strategy_cache=False: nothing outside the checkout steers a run,
        # and the search is paid and seen in every set-up
        cfg = FFConfig(batch_size=batch, seed=ctx.seed32, strategy_cache=False,
                       log_level="warning", **sysm["ffconfig"])
        model = FFModel(cfg)
        gcfg = family.build(model, cell.config, batch)
        facts["seq"] = gcfg.seq
        t0 = time.perf_counter()
        cm = model.compile(AdamOptimizer(alpha=sysm["adam_lr"]),
                           loss_type="sparse_categorical_crossentropy",
                           metrics=[])
        facts["search_s"] = time.perf_counter() - t0
    t_strategy = time.perf_counter()
    with ctx.span("setup/init"):
        cm.init(seed=ctx.seed32)
    x, y = traffic.stride_dataset(gcfg.vocab, gcfg.seq, batch * steps_per_fit,
                                  ctx.seed)
    first = ([a[:batch] for a in x], y[:batch])
    with ctx.span("setup/warmup"):
        # two steps on the first batch: epoch 0's loss is the first step's,
        # taken with the initial parameters
        hist = cm.fit(first[0], first[1], epochs=2, verbose=False)
        first_loss = float(hist[0]["loss"])
        jax.block_until_ready((cm.params, cm.opt_state))
    facts["compile_s"] = time.perf_counter() - t_strategy
    facts["setup_s"] = ctx.since_start()
    leaves = jax.tree_util.tree_leaves(cm.params)
    sharded = [l for l in leaves if not l.sharding.is_fully_replicated]
    emit(fact="strategy", name=cm.strategy.name,
         mesh=dict(cm.machine.mesh_axes), sharded_weights=len(sharded),
         parameter_leaves=len(leaves),
         parameters=int(sum(l.size for l in leaves)),
         predicted_step_s=cm.predicted_step_time(), **ctx.counter.facts())

    compiles_before = ctx.counter.requests
    t0 = time.perf_counter()
    with ctx.span("window"):
        # at least two fit calls: `loss_fell` compares the last with the first
        while (time.perf_counter() - t0 < ctx.seconds
               or len(facts["fit_seconds"]) < 2):
            with ctx.span("fit"):
                _fit_once(cm, x, y, steps_per_fit, facts)
        jax.block_until_ready((cm.params, cm.opt_state))
    window_s = time.perf_counter() - t0
    clean = no_compile_in_window(ctx, compiles_before, "train")
    facts["memory_peak_bytes"] = peak_bytes(list(cm.mesh.devices.flat))
    tokens = facts["steps"] * batch * gcfg.seq
    facts["window_s"] = window_s
    facts["train_tokens_per_s"] = stats.tokens_per_s(tokens, window_s)
    facts["median_step_s"] = statistics.median(
        s / steps_per_fit for s in facts["fit_seconds"])
    facts["predicted_step_s"] = cm.predicted_step_time()
    per_token = family.train_flops_per_token(cell.config, gcfg.seq)
    emit(fact="train_window", steps=facts["steps"], tokens=tokens,
         window_s=window_s, fit_calls=len(facts["fit_seconds"]),
         fit_seconds=facts["fit_seconds"], fit_mean_losses=facts["fit_losses"],
         median_step_s=facts["median_step_s"],
         step_stats={k: facts[k] for k in ("fit_dispatches", "fit_host_syncs",
                                           "fit_barriers")},
         flops_per_token=per_token,
         program_flops_per_token=gcfg.flops_per_token(),
         mfu=None if ctx.peaks is None else flops.mfu(
             facts["train_tokens_per_s"], per_token, ctx.peaks, cell.chips))

    if ctx.trace:
        # one more fit call, shorter where the mix says so (`traced_steps`):
        # a trace holds every operation of every chip
        n_traced = int(tr.get("traced_steps", steps_per_fit))
        traced = _counters()
        xt, yt = [a[:batch * n_traced] for a in x], y[:batch * n_traced]
        with ctx.traced():
            with ctx.span("traced_fit"):
                _fit_once(cm, xt, yt, n_traced, traced)
                jax.block_until_ready((cm.params, cm.opt_state))
        facts["traced_steps"] = traced["steps"]
        facts["fit_losses"] += traced["fit_losses"]
        ctx.trace_window = {"anchor": "bench/traced_fit"}

    # correctness, outside every timed window: the plain reference's loss on
    # the first batch with the initial parameters (made again from the seed;
    # the trained ones are dropped first so that both never lie side by side)
    losses = facts["fit_losses"]
    cm.params = cm.opt_state = None
    cm.init(seed=ctx.seed32)
    ids, pos = [jax.device_put(a, cm.input_sharding(t))
                for a, t in zip(first[0], cm.model.input_tensors)]
    labels = jax.device_put(first[1], cm.label_sharding(first[1].shape))
    ref_loss = float(family.reference_loss(cell.config, cm.params, ids, pos,
                                           labels))
    tol = float(sysm["loss_tolerance"])
    checks = {
        "no_compile_in_window": clean,
        "first_loss_matches_reference": abs(first_loss - ref_loss) <= tol,
        "losses_finite": all(math.isfinite(l) for l in losses + [first_loss]),
        # the last fit call's mean loss against the first fit call's: like
        # against like (means over steps_per_fit steps of the same epoch)
        "loss_fell": len(losses) >= 2 and losses[-1] < losses[0],
    }
    emit(fact="correctness", first_step_loss=first_loss,
         reference_loss=ref_loss, abs_diff=abs(first_loss - ref_loss),
         tolerance=tol, fit_mean_losses=losses, checks=checks)
    facts["correct"] = all(checks.values())
    facts["attempted"] = facts["steps"]
    facts["failed"] = sum(steps_per_fit for l in facts["fit_losses"]
                          if not math.isfinite(l))
    return facts
