"""How close the compiled training step's GRADIENTS are to the plain
reference's, on the chip, at the published widths and the cell's cut: one
sequence of `train_positions` through FFModel -> compile -> cm.fit (ONE Adam
step from zero moments, so the step's own gradients are its first moment over
1 - b1), against jax.grad of harness/reference_afmoe.py (float32, highest
precision) on the same batch and the same initial parameters, by parameter
group as |g - g_ref| / |g_ref| in the Frobenius norm; and the selection
biases after that one step against the reference's rule.

    python benchmarks/grad_check_afmoe.py --workload Trinity-Mini.train-8k --seed 7
    JAX_PLATFORMS=cpu python benchmarks/grad_check_afmoe.py --rehearsal --workload afmoe-tiny.train

Tolerances (TOLERANCE below), with their reasons and the chip's readings
(my chip run, PR 58, seed 7). The program multiplies in bfloat16 with float32
accumulation, the reference in float32 at highest precision: a product's
relative error is about 2^-9 a factor and a gradient is a sum of thousands of
such terms with independent signs, so a group NO routing decides reads 0.5-1.1
% (embedding 0.80, head 0.46, norms 0.83, head norms 1.09, W_q 1.13, W_k 1.13,
W_v 1.00, W_o 1.01, W_g 1.00, dense MLP 0.89, shared expert 0.88): limit 2 %,
which a backward in a lower precision than stated (fp8 products, bfloat16
accumulation: 3 % and more on every group) fails on EACH of them. The router
and the held experts read more (9.3 % and 5.1 % in the median, 9.4 % the
worst expert), and not by precision: a token whose 8th and 9th selection
scores lie within bfloat16 rounding of the hidden state chooses another expert
here than there (`undecided_tokens`: the reference's own count, a layer, of
the tokens whose two scores lie within 2^-8), an expert's gradient then holds
or lacks that token's row among its 512, and the router's gradient is made of
the chosen experts' gates alone: limit 15 %, which says that the routed path's
gradient is there and of the right size and leaves the precision to the
eleven groups above. The BIAS after the step must have the rule's form exactly
(every entry moved by -rate, 0 or +rate less their common mean) and agree with
the reference's own update in all but `BIAS_SIGN_LIMIT` entries a layer: an
expert whose count lies within a few flipped tokens of the mean (512 a layer)
turns its sign. The last line says `"holds": true` where all of that holds."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest as mf  # noqa: E402
from harness.facts import emit  # noqa: E402

B1 = 0.9
# |g - g_ref| / |g_ref| a group; see the module's docstring
TOLERANCE = {"embed": 0.02, "head": 0.02, "norms": 0.02, "head_norms": 0.02,
             "wq": 0.02, "wk": 0.02, "wv": 0.02, "wo": 0.02, "wg": 0.02,
             "dense_mlp": 0.02, "shared_expert": 0.02, "router": 0.15,
             "held_expert": 0.15}
BIAS_SIGN_LIMIT = 8


def bias_steps(after, before, rate: float):
    """The rule's form: (each entry's move in units of `rate` less the
    smallest, which must be 0, 1 or 2 exactly in float32's reach; whether
    it is)."""
    import numpy as np

    move = (np.asarray(after, np.float64) - np.asarray(before, np.float64)) \
        / rate
    steps = move - move.min()
    return np.rint(steps).astype(int), bool(
        np.abs(steps - np.rint(steps)).max() < 1e-3 and np.rint(steps).max() <= 2
        and abs(move.mean()) < 1e-3)


def groups(tree: dict, cfg: dict) -> dict:
    """A reference-layout tree's leaves by parameter group: {group: [leaf]};
    each held expert of each layer is a group of its own (`held_expert`
    reports the worst)."""
    out = {"embed": [tree["embed"]], "head": [tree["head"]],
           "norms": [tree["norm_f"]]}
    for i, layer in enumerate(tree["layers"]):
        for name in ("norm_in", "norm_post_attn", "norm_pre_mlp",
                     "norm_post_mlp"):
            out["norms"].append(layer[name])
        out.setdefault("head_norms", []).extend([layer["q_norm"],
                                                 layer["k_norm"]])
        for name in ("wq", "wk", "wv", "wo", "wg"):
            out.setdefault(name, []).append(layer[name])
        if "router" not in layer:
            out.setdefault("dense_mlp", []).extend([layer["w_in"],
                                                    layer["w_out"]])
            continue
        out.setdefault("router", []).append(layer["router"])
        out.setdefault("shared_expert", []).extend([layer["shared_in"],
                                                    layer["shared_out"]])
        for e in range(cfg["num_experts"]):
            out[f"held_expert.l{i}.e{e}"] = [layer["experts_in"][e],
                                             layer["experts_out"][e]]
    return out


def distance(got: list, want: list) -> float:
    """On the host, in float64."""
    import numpy as np

    num = sum(float(np.sum(np.square(np.asarray(g, np.float64)
                                     - np.asarray(w, np.float64))))
              for g, w in zip(got, want))
    den = sum(float(np.sum(np.square(np.asarray(w, np.float64))))
              for w in want)
    return (num / den) ** 0.5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the tiny cell of rehearsal_afmoe.json, any backend")
    args = ap.parse_args(argv)
    manifest = mf.load_manifest(BENCH_DIR / "rehearsal_afmoe.json"
                                if args.rehearsal else mf.MANIFEST)
    cell = mf.load_cell(manifest, args.workload)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from families import afmoe as family
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from harness import reference_afmoe as reference
    from harness import traffic

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        raise SystemExit("grad_check_afmoe.py compares on the chip; "
                         "--rehearsal runs the tiny cell anywhere")
    cfg, seed32 = cell.config, args.seed % (2 ** 31 - 1)
    model = FFModel(FFConfig(batch_size=1, seed=seed32, strategy_cache=False,
                             log_level="warning", **cell.system["ffconfig"]))
    gcfg = family.build(model, cfg, 1)
    cm = model.compile(AdamOptimizer(alpha=cell.system["adam_lr"], beta1=B1),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=seed32)
    x, y = traffic.stride_dataset(gcfg.vocab, gcfg.seq, 1, args.seed)
    ids, pos = (jnp.asarray(a) for a in x)
    labels = jnp.asarray(y)
    held = family.held(cfg)

    # the step first: it donates its parameters, so what it started from is
    # kept on the HOST meanwhile, and the program's trees are dropped before
    # the reference's gradients are taken (two sets of float32 gradients and
    # Adam's moments do not fit beside the reference's activations)
    start_host = jax.tree_util.tree_map(np.asarray, (cm.params, cm.state))
    loss = cm.fit(x, y, epochs=1, verbose=False)[0]["loss"]
    got = family.reference_params(
        jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - B1),
                               cm.opt_state[0].mu),
        {k: np.zeros(v.shape, np.float32) for k, v in cm.state.items()}, cfg)
    got_bias = [np.asarray(cm.state[f"l{i}_moe/score_bias"])
                for i in range(cfg["num_dense_layers"],
                               cfg["num_hidden_layers"])]
    cm.params = cm.opt_state = cm.state = None
    start = family.reference_params(*jax.device_put(start_host), cfg)
    ref_loss, counts, undecided = reference.loss_and_counts(
        start, ids, pos, labels, cfg, held)
    want = jax.tree_util.tree_map(np.asarray, jax.jit(
        lambda p: reference.gradients(p, ids, pos, labels, cfg, held))(start))
    expert_layers = start["layers"][cfg["num_dense_layers"]:]
    want_bias = [np.asarray(reference.bias_update(
        layer["bias"], c, cfg["load_balance_coeff"]))
        for layer, c in zip(expert_layers, counts)]
    first_bias = [np.asarray(layer["bias"]) for layer in expert_layers]
    del start

    by_group, want_groups = groups(got, cfg), groups(want, cfg)
    read = {name: distance(by_group[name], want_groups[name])
            for name in by_group}
    experts = {n: v for n, v in read.items() if n.startswith("held_expert.")}
    table = {n: v for n, v in read.items() if not n.startswith("held_expert.")}
    table["held_expert"] = max(experts.values())
    inside = {n: v <= TOLERANCE[n] for n, v in table.items()}
    rate = cfg["load_balance_coeff"]
    formed = [bias_steps(g, f, rate) for g, f in zip(got_bias, first_bias)]
    wanted = [bias_steps(w, f, rate) for w, f in zip(want_bias, first_bias)]
    bias_form = [ok for _steps, ok in formed]
    # experts whose move is another than the reference's: their count lay
    # within the flipped tokens of the mean
    turned = [int(np.sum(a != b)) if a.max() == b.max() else len(a)
              for (a, _), (b, _) in zip(formed, wanted)]
    emit(fact="grad_check", workload=cell.name, seed=args.seed,
         tokens=int(ids.size), loss=float(loss), reference_loss=float(ref_loss),
         distance_by_group=table, tolerance=TOLERANCE, inside=inside,
         held_expert_median=sorted(experts.values())[len(experts) // 2],
         held_expert_worst=max(experts, key=experts.get),
         undecided_tokens=[int(u) for u in undecided],
         bias_has_the_rules_form=bias_form, bias_signs_turned=turned,
         bias_sign_limit=BIAS_SIGN_LIMIT,
         bias_step=[float(np.max(np.abs(g - f)))
                    for g, f in zip(got_bias, first_bias)])
    holds = all(inside.values()) and all(bias_form) \
        and max(turned) <= BIAS_SIGN_LIMIT
    print(json.dumps({"holds": holds, "worst": max(table, key=table.get),
                      "worst_distance": max(table.values()),
                      "bias_has_the_rules_form": bias_form,
                      "bias_signs_turned": turned}), flush=True)
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main())
