"""The `jamba` family in the benchmark: its tiny cell through
rehearse_jamba.py (the serving cell's whole control flow on the CPU backend,
prompts prefilled in chunks that start the Mamba layers from their slot's
state; the family's own manifest rehearsal_jamba.json, since rehearsal.json
is the benchmark's and not a model PR's to edit), the full-size
configuration against the published keys, the functions that count what the
new metrics need, and how tight the comparison is: the logits check with its
wrong references (logits_check_jamba.py) at the tiny size. Nothing here
times anything. New entries of the manifest are found by membership: nothing
here asserts that an entry is the last, or how many there are."""

import json
import subprocess
import sys

import pytest

from test_granitemoehybrid_rehearsal import BENCH, CPU_ENV, ROOT

from harness import flops_jamba as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402

CONFIG = "AI21-Jamba2-3B"
CELL = CONFIG + ".serve-longprompt"
TINY = "jamba-tiny.serve"
# three and not the issue's seven: `per_layer` holds 128 entries at most and
# had 125 (the mixer's device time, the state's bytes a step and the chunks a
# request wait for a benchmark PR to make room: PERF.md, Open questions)
NEW = ["prefill_mfu.jamba", "decode_step_hbm_roofline.jamba",
       "selective_scan_roofline.jamba"]
APPENDED = ["wave_attention_device_ms", "decode_attention_device_ms_per_step",
            "prefill_useful_token_share", "queue_wait_p95_ms",
            "prefill_device_ms", "decode_step_device_ms",
            "device_idle.serve"]
REHEARSAL = "rehearsal_jamba.json"
# the catalog row's `config` (model-configs guide, architectures.jsonl),
# every key as published
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    # the benchmark's own rehearsal manifest is as it was
    assert "jamba" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_jamba.py"), "--workload",
         TINY, "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    cell = mf.load_cell(mf.load_manifest(), CELL)
    assert set(NEW) | set(APPENDED) <= set(last["would_report"])
    assert set(last["would_report"]) == {m["name"] for m in cell.per_layer}
    # no experts, no latent, no indexer, no window in this model
    assert not {"wave_experts_device_ms", "moe_rows_computed_share.prefill",
                "latent_cache_read_mb_per_step.decode",
                "sparse_keys_kept_share.decode.keye",
                "wave_window_attend_device_ms.mellum"} \
        & set(last["would_report"])
    # prompts of 17-80 tokens in chunks of 32: more chunk calls than requests
    window = next(l for l in lines if l.get("fact") == "serve_window")
    assert window["prefill_waves"] > window["completed"]
    assert window["shed"] == 0 and window["accounted"]


def test_the_logits_check_parts_the_program_from_its_wrong_references():
    """Prefill in chunks through the program the scheduler runs (a prompt
    that ends on a chunk's edge and one that ends inside a chunk), then
    decode through state and pages, against the reference's full forward, on
    logits: the float32 tiny program within 1e-4 of the scale (the order of
    its sums); the reference with a bf16 state, with a bf16 decay and with
    fp8 weights far outside it."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "logits_check_jamba.py"),
         "--rehearsal", "--workload", TINY, "--seeds", f"5,{2 ** 31 + 11}",
         "--steps", "16", "--tolerance", "1e-4"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:] + done.stdout[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["holds"] is True
    wrong = last["wrong_mean_diff_over_scale"]
    assert set(wrong) == {"bf16_state_reference", "bf16_decay_reference",
                          "fp8_reference"}
    assert last["program_mean_diff_over_scale"] < 1e-5 < 3e-4 \
        < min(wrong.values())
    assert last["program_served_gap_ulps"] == 0
    assert lines[0]["state_kinds"] == "paged_kv+recurrent"
    assert lines[0]["chunk"] == 32
    assert lines[0]["ends_on_a_chunks_edge"] == [True, False]


def test_the_full_size_configuration_holds_the_published_keys():
    cfg = mf.read_named("configs", CONFIG)
    for key, value in PUBLISHED.items():
        assert key in cfg and cfg[key] == value, key
    assert cfg["family"] == "jamba" and cfg["reduced"] == []
    assert cfg["source"] == ("https://huggingface.co/ai21labs/AI21-Jamba2-3B/"
                             "blob/main/config.json")
    assert (cfg["n_embd"], cfg["n_head"]) == (2560, 20)
    assert cfg["assumed"]["serve_positions"] == 16384 + 512
    assert cfg["assumed"]["weights_dtype"] == "bfloat16"
    assert {"tie_word_embeddings", "weights", "max_position_embeddings"} \
        <= set(cfg["departures"])
    kinds = flops.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    sysm = mf.read_named("workloads", CELL)
    assert sysm["ffconfig"] == {"compute_dtype": "bfloat16",
                                "mesh_shape": {"data": 1},
                                "serve_prefill_chunk": 2048}
    assert (sysm["max_batch_slots"], sysm["max_decode_len"],
            sysm["kv_page_size"]) == (16, 512, 16)
    assert set(sysm["traffic"]) == {"rate_rps"}


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cell = mf.load_cell(manifest, CELL)
    listed = {m["name"] for m in cell.per_layer}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        reader = mf.read_named("metrics", name)["reader"]
        assert reader in ("span_need", "scope_roofline")
        assert (BENCH / "readers" / f"{reader}.py").is_file()
    assert set(NEW) | set(APPENDED) <= listed
    assert len(manifest["per_layer"]) <= 128    # the driver's limit of form
    assert {p.stem for p in (BENCH / "metrics").glob("*.jamba.json")} \
        == set(NEW)
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if m["name"].rsplit(".", 1)[-1] in (
                    "keye", "lfm2", "brumby", "ling", "nemotron", "gigachat",
                    "granite", "mellum", "trinity", "train"):
                assert CELL not in m.get("workloads", [CELL + "!"])
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == []
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "serve-longprompt"
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_flop_and_byte_functions_against_the_program_and_the_issue():
    sys.path.insert(0, str(ROOT))
    from families import jamba as family

    cfg = mf.read_named("configs", CONFIG)
    g = family.program_config(cfg)
    assert flops.param_count(cfg) == g.param_count() == 3197109632
    assert flops.matmul_params_per_token(cfg) == g.matmul_params_per_token()
    assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
    assert flops.state_bytes_per_slot(cfg) == g.state_bytes_per_slot() \
        == 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert flops.cache_bytes_per_token(cfg) * 2 == 1024   # two layers: 1 KB
    # a full chunk of 2048 behind 6144 positions
    keys = 2 * sum(6144 + t + 1 for t in range(2048))
    counters = {"mamba_layers": 26.0, "mamba_rows": 26 * 2048.0,
                "full_keys_seen": float(keys)}
    need = flops.prefill_chunk_need(cfg, {}, {}, counters)
    body = 2 * 2048 * (g.matmul_params_per_token() - 2560 * 65536)
    assert need["flops"] == pytest.approx(
        body + 26 * 2048 * 8 * 5120 * 16 + 4 * keys * 2560
        + 2 * 2560 * 65536)
    assert 11.5e12 < body < 11.9e12         # the issue's 11.7 TFLOP a chunk
    scan = flops.selective_scan_need(cfg, {}, {}, counters)
    assert scan["flops"] == 26 * 2048 * 8 * 5120 * 16
    assert scan["bytes"] == 26 * (2048 * (4 * 5120 * 2 + 2 * 16 * 4)
                                  + 2 * 16 * 5120 * 4)
    step = flops.decode_step_need(
        cfg, {}, {}, {"ssm_state_bytes": 4 * 2 * 9318400.0,
                      "full_kv_bytes_needed": 4 * 2 * 512 * 8000.0})
    weights = 2 * (3197109632 - 65536 * 2560)
    assert step["bytes"] == pytest.approx(
        weights + 2 * 4 * 2560 + 4 * 2 * 9318400 + 4 * 2 * 512 * 8000)
