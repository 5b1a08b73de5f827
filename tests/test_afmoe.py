"""AFMoE decoders (flexflow_tpu/models/afmoe.py: gated attention under a
window with rotary positions on the sliding layers and over the whole context
with NO positions on the full ones, four norms a layer, a sigmoid router whose
selection bias is the layer's STATE and moves inside the training step, a
shared expert, an embedding scaled by sqrt(d)) against the plain reference
(benchmarks/harness/reference_afmoe.py), at a small size on the CPU with
seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone: about 1e-6 of the result's scale. RTOL 1e-4
leaves two orders for that and none for a fault: a gate left out, a rotation
on a full layer or a bias that enters the gates moves a logit row by 1e-2 and
more.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.models import AfmoeConfig, build_afmoe  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY  # noqa: E402
from flexflow_tpu.serving import (compile_serving,  # noqa: E402
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from families import afmoe as family  # noqa: E402
from harness import flops_afmoe as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_afmoe as reference  # noqa: E402
from served import off_by  # noqa: E402

RTOL = 1e-4
B1 = 0.9        # Adam's first-moment decay: after one step mu = (1 - B1) g


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def tiny_file(**changed) -> dict:
    return dict(mf.read_named("configs", "afmoe-tiny"), **changed)


# (configuration, batch) -> what `init(seed=3)` draws for it: the same under
# every rate and policy, and each `init` compiles its three programs anew
# (6 s a call, a dozen calls a module), so drawn once a process
_DRAWN = {}


def compiled(g, batch=2, lr=1e-3, **kw):
    m = FFModel(ffconfig(batch, **kw))
    build_afmoe(m, g, batch=batch)
    cm = m.compile(AdamOptimizer(alpha=lr),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    key = repr(g), batch
    if key not in _DRAWN:
        cm.init(seed=3)
        _DRAWN[key] = jax.tree_util.tree_map(
            np.asarray, (cm.params, cm.opt_state, cm.state))
    # fresh buffers a model: a step donates the ones it is handed
    cm.params, cm.opt_state, cm.state = jax.tree_util.tree_map(
        jnp.asarray, _DRAWN[key])
    cm._iteration = 0
    return cm


def batch_of(g, rows, seed=5):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, g.vocab, (rows, g.seq)).astype(np.int32)
    pos = np.ascontiguousarray(np.broadcast_to(
        np.arange(g.seq, dtype=np.int32), ids.shape))
    return ids, pos, np.roll(ids, -1, axis=1)


def close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30)


def held_tiny():
    """The tiny file's holder: 4 of 8 experts."""
    g, cfg = AfmoeConfig.tiny(seq=32), tiny_file()
    g.experts_held = family.held(cfg)
    return g, cfg


def test_the_tiny_file_is_the_programs_tiny_config():
    cfg = tiny_file()
    g = AfmoeConfig.tiny(seq=cfg["assumed"]["train_positions"])
    g.experts_held = family.held(cfg)
    assert family.program_config(cfg) == g


def test_logits_loss_and_every_gradient_against_the_reference():
    """Through the compiled step itself: after ONE Adam step from zero
    moments the first moment is (1 - b1) g, so the step's own gradients are
    read off its optimizer state."""
    g, cfg = held_tiny()
    cm = compiled(g)
    ids, pos, labels = batch_of(g, 2)
    held = family.held(cfg)
    rp = family.reference_params(cm.params, cm.state, cfg)
    assert close(cm.forward(ids, pos), reference.logits(rp, ids, pos, cfg, held))
    want_loss = reference.next_token_loss(rp, ids, pos, labels, cfg, held)
    want = reference.gradients(rp, ids, pos, labels, cfg, held)
    loss = cm.fit([ids, pos], labels, epochs=1, verbose=False)[0]["loss"]
    assert abs(loss - float(want_loss)) <= 1e-5
    got = family.reference_params(
        jax.tree_util.tree_map(lambda m: m / (1 - B1), cm.opt_state[0].mu),
        {f"l{i}_moe/score_bias": 0.0
         for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"])},
        cfg)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want) > 60
    for path, grad in flat_got:
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):       # state: no gradient anywhere
            assert not np.any(np.asarray(flat_want[path]))
            continue
        assert np.abs(np.asarray(flat_want[path])).max() > 0, name
        assert close(grad, flat_want[path], 2e-4), name


def test_the_bias_is_state_and_moves_by_the_references_rule():
    """Three steps, each checked: the bias after a step is the reference's
    rule applied to the reference's own routed counts under the parameters
    the step STARTED from. Adam holds no moments for it, a checkpoint holds
    it, and evaluation leaves it where it is."""
    g, cfg = held_tiny()
    cm = compiled(g, lr=1e-2)
    held = family.held(cfg)
    moe = [f"l{i}_moe" for i in range(g.dense_layers, g.layers)]
    assert sorted(cm.state) == [f"{name}/score_bias" for name in moe]
    for tree in (cm.params, cm.opt_state[0].mu, cm.opt_state[0].nu):
        assert all("score_bias" not in tree[name] for name in moe)
    first = {name: np.asarray(cm.state[f"{name}/score_bias"]) for name in moe}
    assert 0 < np.abs(first[moe[0]]).max() <= 0.01      # drawn, not zeros
    for step in range(3):
        ids, pos, labels = batch_of(g, 2, seed=20 + step)
        rp = family.reference_params(cm.params, cm.state, cfg)
        _, counts, _ = reference.loss_and_counts(rp, ids, pos, labels, cfg,
                                                 held)
        want = [reference.bias_update(layer["bias"], c,
                                      cfg["load_balance_coeff"])
                for layer, c in zip(rp["layers"][g.dense_layers:], counts)]
        assert int(counts.sum()) == len(moe) * ids.size * g.experts_per_tok
        cm.fit([ids, pos], labels, epochs=1, verbose=False)
        for name, b in zip(moe, want):
            got = np.asarray(cm.state[f"{name}/score_bias"])
            assert np.array_equal(got, np.asarray(b)), (step, name)
            assert abs(got.sum() - first[name].sum()) < 1e-6  # its mean stays
    before = jax.tree_util.tree_map(np.asarray, cm.state)
    cm.evaluate([ids, pos], labels)
    for name, leaf in before.items():
        assert np.array_equal(leaf, np.asarray(cm.state[name]))
    stats = [s for s in tel.ring_spans() if s.name == "fit/step_stats"][-1]
    assert stats.args["moe_bias_layers"] == len(moe)
    assert stats.args["moe_router_load_mean"] * g.num_experts \
        == len(moe) * ids.size * g.experts_per_tok
    assert stats.args["moe_held_pairs"] <= stats.args["moe_routed_pairs"]
    s = g.seq
    band = s * (s + 1) // 2 - (s - g.window) * (s - g.window + 1) // 2
    assert stats.args["window_keys_seen"] == 4 * 2 * g.heads * band
    assert stats.args["full_keys_seen"] == 2 * g.heads * s * (s + 1) // 2
    assert stats.args["window_keys_causal"] \
        == 4 * 2 * g.heads * s * (s + 1) // 2


def test_a_checkpoint_holds_the_bias(tmp_path):
    g, _cfg = held_tiny()
    cm = compiled(g)
    ids, pos, labels = batch_of(g, 2)
    cm.fit([ids, pos], labels, epochs=1, verbose=False)
    moved = jax.tree_util.tree_map(np.asarray, cm.state)
    cm.save_checkpoint(str(tmp_path / "ck"), block=True)
    other = compiled(g)
    assert not np.array_equal(np.asarray(other.state["l1_moe/score_bias"]),
                              moved["l1_moe/score_bias"])
    other.load_checkpoint(str(tmp_path / "ck"))
    assert sorted(other.state) == sorted(moved)
    for name, leaf in moved.items():
        assert np.array_equal(np.asarray(other.state[name]), leaf)


def test_the_holders_parts_add_up_to_the_uncut_layer():
    """One expert layer, eight holders of one expert each: their parts,
    through the program's own lowering, with the shared expert counted once,
    are the uncut reference's layer."""
    g, cfg = AfmoeConfig.tiny(seq=32), tiny_file()
    d, width, experts = g.d_model, g.expert_width, g.num_experts
    sh = reference.shape(cfg)
    rng = np.random.default_rng(2)
    p = {"router": rng.normal(size=(d, experts)).astype(np.float32),
         "bias": rng.uniform(-0.2, 0.2, experts).astype(np.float32),
         "experts_in": rng.normal(size=(experts, d, 2 * width)).astype(
             np.float32) / 8,
         "experts_out": rng.normal(size=(experts, width, d)).astype(
             np.float32) / 8,
         "shared_in": rng.normal(size=(d, 2 * width)).astype(np.float32) / 8,
         "shared_out": rng.normal(size=(width, d)).astype(np.float32) / 8}
    x = rng.normal(size=(2, g.seq, d)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.stack([reference.expert_layer(
            p, jnp.asarray(row), (0, experts), sh)[0] for row in x])
        _, counted = reference.expert_layer(p, jnp.asarray(x[0]),
                                            (0, experts), sh)
        assert int(counted[:-1].sum()) == g.seq * g.experts_per_tok
        shared = np.stack([reference.gated_mlp(
            jnp.asarray(row), p["shared_in"], p["shared_out"]) for row in x])
    total = np.array(shared)
    for e in range(experts):
        m = FFModel(ffconfig(2))
        t = m.create_tensor([2, g.seq, d], name="x")
        m.moe_layer(t, experts, g.experts_per_tok, width, (e, e + 1),
                    scoring="sigmoid", norm_topk_prob=True,
                    routed_scaling_factor=g.route_scale, score_bias="state",
                    name="moe")
        cm = m.compile(AdamOptimizer(alpha=0.0), loss_type="identity",
                       metrics=[])
        part, _ = cm.forward_fn(
            {"moe": {"router": p["router"], "w_in": p["experts_in"][e:e + 1],
                     "w_out": p["experts_out"][e:e + 1]}},
            {"moe/score_bias": jnp.asarray(p["bias"])}, [jnp.asarray(x)],
            False, None)
        total += np.asarray(part[0])
    assert close(total, whole)
    assert not close(total - shared, whole, 1e-2)   # the shared part matters


def test_a_full_layer_reads_no_positions_and_a_sliding_one_does():
    """Positions moved apart (doubled): a model of full layers alone computes
    the same logits, one with a sliding layer does not. (A shift alone would
    not tell them apart: rotary scores depend on differences.)"""
    ids, pos, _ = batch_of(AfmoeConfig.tiny(seq=32), 2)
    for kinds, same in ((("full_attention",) * 2, True),
                        (("full_attention", "sliding_attention"), False)):
        g = AfmoeConfig.tiny(seq=32)
        g.layer_types = kinds
        cm = compiled(g)
        a, b = cm.forward(ids, pos), cm.forward(ids, 2 * pos)
        assert np.array_equal(np.asarray(a), np.asarray(b)) == same


def test_a_window_that_holds_the_sequence_is_plain_causal_attention():
    g = AfmoeConfig.tiny(seq=16)
    wide = AfmoeConfig.tiny(seq=16)
    wide.window = 16
    ids, pos, _ = batch_of(g, 2)
    plain = AfmoeConfig.tiny(seq=16)
    plain.window = 4096
    assert np.array_equal(np.asarray(compiled(wide).forward(ids, pos)),
                          np.asarray(compiled(plain).forward(ids, pos)))
    assert not close(compiled(g).forward(ids, pos),
                     compiled(wide).forward(ids, pos), 1e-3)


@pytest.mark.parametrize("policy", ("remat_blocks", "remat"))
def test_remat_changes_no_gradient_and_keeps_the_counters(policy):
    g, _cfg = held_tiny()
    ids, pos, labels = batch_of(g, 2)
    grads = {}
    for kw in ({}, {policy: True}):
        cm = compiled(g, **kw)
        cm.fit([ids, pos], labels, epochs=1, verbose=False)
        stats = [s for s in tel.ring_spans()
                 if s.name == "fit/step_stats"][-1].args
        grads[bool(kw)] = (cm.opt_state[0].mu, cm.state, stats)
    for a, b in zip(jax.tree_util.tree_leaves(grads[False][:2]),
                    jax.tree_util.tree_leaves(grads[True][:2])):
        assert close(a, b, 1e-5)
    assert grads[False][2]["moe_held_pairs"] == grads[True][2]["moe_held_pairs"]
    assert sorted(grads[False][2]) == sorted(grads[True][2])


def test_checkpoint_units_end_at_the_residual_stream():
    from flexflow_tpu.compiler.lowering import checkpoint_units
    from flexflow_tpu.core.graph import topo_order

    g = AfmoeConfig.tiny(seq=16)
    m = FFModel(ffconfig(2))
    _, logits = build_afmoe(m, g, batch=2)
    order = topo_order(m.layers)
    units = checkpoint_units(order, [logits], {l.name: "block" for l in order})
    names = [[l.name for l in u] for u in units]
    assert names[0] == ["embed", "embed_scale"]
    assert names[1] == ["l0_norm_in", "l0_attn", "l0_norm_post_attn", "l0_res1"]
    assert names[2][0] == "l0_norm_pre_mlp" and names[2][-1] == "l0_res2"
    assert ["l1_norm_pre_mlp"] in names      # two branches read its output
    # the last residual has one reader: the final norm and the head close
    # the last layer's unit
    assert names[-1][0] == "l4_moe" and names[-1][-2:] == ["norm_f", "lm_head"]
    assert sum(len(u) for u in units) == len(order)
    assert checkpoint_units(order, [logits], {}) == [[l] for l in order]


def engine_for(g, chunk=16, slots=2):
    model = FFModel(ffconfig(slots, serve_prefill_chunk=chunk))
    build_afmoe(model, g, batch=slots, with_valid=True)
    eng = compile_serving(model, max_batch_slots=slots, max_decode_len=8,
                          kv_page_size=4)
    eng.init(seed=3)
    return eng


def served_reference_params(eng, cfg):
    """Served, the bias is a weight of the expert layers' twins."""
    state = {f"{name}/score_bias": leaves["score_bias"]
             for name, leaves in eng.params.items() if "score_bias" in leaves}
    return family.reference_params(eng.params, state, cfg)


def test_prefill_in_chunks_then_decode_against_the_references_forward():
    """The gate and the position-free layer in the cache forms: a prompt of
    29 in two chunks of 16 (it laps the sliding layers' ring), then steps;
    every logits row is the reference's full forward over the slot's
    tokens."""
    g, cfg = AfmoeConfig.tiny(seq=48), tiny_file()
    g.experts_held = family.held(cfg)
    eng = engine_for(g)
    kv, c = eng.kv, eng.chunk_tokens
    rp, held = served_reference_params(eng, cfg), family.held(cfg)
    prompt = [int(t) for t in np.random.default_rng(11).integers(0, g.vocab, 29)]

    def want_row(seq):
        ids = np.asarray([seq], np.int32)
        pos = np.arange(len(seq), dtype=np.int32)[None]
        return np.asarray(reference.logits(rp, ids, pos, cfg, held))[0, -1]

    kv.admit(0, len(prompt), len(prompt) + 8, prefilling=True)
    kv.push()
    for done in range(0, len(prompt), c):
        part = prompt[done:done + c]
        ids = np.zeros((1, c), np.int32)
        ids[0, :len(part)] = part
        lengths, context = np.asarray([len(part)]), np.asarray([done])
        tok, state = eng.prefill_chunk(
            eng.params, kv.state,
            positions_valid_prompt_inputs(ids, lengths, context),
            kv.prefill_row(0)[None], context, lengths)
        state.pop(STATS_KEY)
        kv.adopt(state)
    kv.activate(0, len(prompt))
    kv.push()
    seq = prompt + [int(np.asarray(tok)[0])]
    assert seq[-1] == int(want_row(prompt).argmax())
    for _ in range(4):
        nxt = np.zeros((eng.slots, 1), np.int32)
        nxt[0, 0] = seq[-1]
        logits, state = eng.decode_step(
            eng.params, kv.state,
            positions_valid_step_inputs(jnp.asarray(nxt), kv.state))
        state.pop(STATS_KEY)
        kv.adopt(state)
        kv.sync_after(1)
        row = np.asarray(logits)[0, 0]
        assert off_by(row, want_row(seq)) <= RTOL, len(seq)
        seq.append(int(row.argmax()))


def test_the_flash_path_is_taken_under_a_window_and_says_so():
    """impl='flash' under a window no longer raises: the interpreted kernels
    against the masked XLA form of the same layer, forward and gradients,
    grouped K/V heads read through the block index."""
    outs = {}
    for impl in ("flash", "xla"):
        m = FFModel(ffconfig(1))
        x = m.create_tensor([1, 256, 32], name="x")
        pos = m.create_tensor([1, 256], DataType.INT32, name="p")
        m.multihead_attention(x, x, x, 4 * 128, 4, bias=False, causal=True,
                              num_kv_heads=2, positions=pos, qk_norm=1e-5,
                              out_dim=32, window=100, output_gate=True,
                              impl=impl, name="attn")
        cm = m.compile(AdamOptimizer(alpha=0.0), loss_type="identity",
                       metrics=[])
        cm.init(seed=3)
        xs = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32))
        ps = jnp.arange(256, dtype=jnp.int32)[None]

        def loss(params, xs):
            out, _ = cm.forward_fn(params, {}, [xs, ps], True, None)
            return jnp.sum(jnp.sin(out[0]))

        outs[impl] = jax.value_and_grad(loss, (0, 1))(cm.params, xs)
    spans = [s for s in tel.ring_spans() if s.args
             and s.args.get("layer") == "attn"][-2:]
    assert [s.name for s in spans] == ["flash/window", "xla/masked"]
    # heads of 128: the merged entry, the head norm and the rotation on the
    # merged axis before it (PR 63: kernels/head_turn.py)
    assert tel.ring_spans("lower/flash_attention")[-1].args["entry"] \
        == "merged"
    assert spans[0].args["window"] == 100 and spans[0].args["flash_tile_q"]
    assert spans[0].args["flash_tiles_visited"] \
        < spans[0].args["flash_tiles_total"]
    for a, b in zip(jax.tree_util.tree_leaves(outs["flash"]),
                    jax.tree_util.tree_leaves(outs["xla"])):
        assert close(a, b, 2e-4)


def test_the_configuration_file_against_the_catalog_and_the_issue():
    import json

    cfg = mf.read_named("configs", "Trinity-Mini")
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl"
                ).exists() else []
    row = [r for r in rows if r["name"] == "Trinity-Mini"]
    if row:
        assert cfg["source"] == row[0]["source_url"]
        differ = sorted(k for k, v in row[0]["config"].items()
                        if cfg.get(k, "absent") != v)
        assert differ == sorted(cfg["reduced"])
    widths = {"hidden_size": 2048, "intermediate_size": 6144, "head_dim": 128,
              "moe_intermediate_size": 1024, "num_attention_heads": 32,
              "num_key_value_heads": 4, "num_experts_per_tok": 8,
              "sliding_window": 2048, "num_shared_experts": 1}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    for key in ("published", "deployment", "assumed", "departures", "why"):
        assert cfg[key]
    assert cfg["layer_types"] == ["sliding_attention", "sliding_attention",
                                  "full_attention", "sliding_attention",
                                  "sliding_attention"]
    assert flops.param_count(cfg, published=True) == 26_123_974_400
    assert flops.param_count(cfg) == 705_474_304
    g = family.program_config(cfg)
    assert g.param_count() == 705_474_304
    assert (g.num_experts, g.experts_held, g.vocab, g.seq) \
        == (128, (0, 16), 25024, 8192)
    assert g.flops_per_token() == flops.train_flops_per_token(cfg, g.seq)
    assert abs(g.flops_per_token() / 2.21e9 - 1) < 0.01     # ISSUE 58's count
    cell = mf.load_cell(mf.load_manifest(), "Trinity-Mini.train-8k")
    assert cell.traffic["global_batch"] * g.seq == 16384
    assert (cell.traffic["steps_per_fit"], cell.traffic["traced_steps"]) \
        == (10, 4)
    assert cell.system["ffconfig"]["compute_dtype"] == "bfloat16"
    need = flops.train_step_need(
        cfg, cell.system, cell.traffic,
        {"steps": 1, "moe_held_pairs": 4 * 16384 * 8 / 8,
         "window_keys_seen": 4 * 2 * 32 * g.keys_seen("sliding_attention"),
         "full_keys_seen": 2 * 32 * g.keys_seen("full_attention")})
    assert abs(need["flops"] / (16384 * g.flops_per_token()) - 1) < 1e-9


def test_the_cells_reference_loss_is_the_references():
    """What the cell's run calls (families/afmoe.py reference_loss: a row at
    a time through one compiled program of a row, the bias read off the
    model `build` made) against the reference's loss of the whole batch."""
    cfg = tiny_file()
    m = FFModel(ffconfig(2))
    g = family.build(m, cfg, 2)
    cm = m.compile(AdamOptimizer(alpha=1e-3),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    ids, pos, labels = batch_of(g, 2)
    rp = family.reference_params(cm.params, cm.state, cfg)
    held = family.held(cfg)
    whole = jax.jit(lambda p: reference.next_token_loss(
        p, ids, pos, labels, cfg, held))(rp)
    assert abs(float(family.reference_loss(cfg, cm.params, ids, pos, labels))
               - float(whole)) < 1e-6


def test_the_references_blocks_change_no_value(monkeypatch):
    """The reference's rolled loops (blocks of queries, blocks of tokens, the
    scan over the held experts) against itself with one block of each."""
    g, cfg = held_tiny()
    cm = compiled(g)
    ids, pos, labels = batch_of(g, 2)
    rp = family.reference_params(cm.params, cm.state, cfg)
    held = family.held(cfg)
    whole = reference.loss_and_counts(rp, ids, pos, labels, cfg, held)
    grads = reference.gradients(rp, ids, pos, labels, cfg, held)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 4)
    blocked = reference.loss_and_counts(rp, ids, pos, labels, cfg, held)
    assert abs(float(whole[0]) - float(blocked[0])) < 1e-6
    assert np.array_equal(whole[1], blocked[1])
    assert np.array_equal(whole[2], blocked[2])
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(reference.gradients(
                        rp, ids, pos, labels, cfg, held))):
        assert close(b, a, 1e-4)
