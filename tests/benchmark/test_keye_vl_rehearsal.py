"""The `keye_vl` family in the benchmark: its tiny cell through
rehearse_keye_vl.py (the serving cell's whole control flow on the CPU backend,
prompts prefilled in chunks over the slot's own cache; the family's own
manifest rehearsal_keye_vl.json, since rehearsal.json is the benchmark's and
not a model PR's to edit), the metrics this family brought, read from a
hand-made ring and a hand-made reduced trace (the share of a slot's keys a
step keeps, the chunks a request, the two shares of a peak through
readers/span_need.py, the two scopes' shares of their rooflines through
readers/scope_roofline.py over the decode program), and how tight the
comparison is: the logits check with its three wrong references
(logits_check_keye_vl.py) at the tiny size. Nothing here times anything. New
entries of the manifest are found by membership: nothing here asserts that
an entry is the last, or how many there are."""

import json
import subprocess
import sys

import pytest

from test_granitemoehybrid_rehearsal import (BENCH, CPU_ENV, MS, ROOT, SKEW,
                                             Ring, Run, read)

from harness import flops_keye_vl as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402

CONFIG = "Keye-VL-2.0-30B-A3B"
CELL = CONFIG + ".serve-longprompt"
TINY = "keye-vl-tiny.serve"
NEW = ["prefill_mfu.keye", "decode_step_hbm_roofline.keye",
       "wave_sparse_indexer_device_ms.keye",
       "decode_sparse_indexer_device_ms_per_step.keye",
       "sparse_index_hbm_roofline.decode.keye",
       "sparse_attend_hbm_roofline.decode.keye",
       "sparse_keys_kept_share.decode.keye", "prefill_chunks_per_request.keye",
       "moe_experts_hit_share.decode.keye", "moe_held_pair_share.decode.keye",
       "moe_expert_load_max_over_mean.decode.keye",
       "moe_experts_roofline.decode.keye"]
APPENDED = ["moe_rows_computed_share.prefill", "wave_experts_device_ms",
            "decode_experts_device_ms_per_step", "wave_attention_device_ms",
            "decode_attention_device_ms_per_step",
            "prefill_useful_token_share", "queue_wait_p95_ms"]
REHEARSAL = "rehearsal_keye_vl.json"
EXPERT = 9437184                # one expert's three matrices, bf16 bytes
LAYERS = 6


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    # the benchmark's own rehearsal manifest is as it was
    assert "keye" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_keye_vl.py"), "--workload",
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
    # no recurrent state, no latent, no linear attention in this model
    assert not {"wave_mixer_device_ms", "state_commit_ms",
                "latent_cache_read_mb_per_step.decode"} \
        & set(last["would_report"])
    # prompts of 17-64 tokens in chunks of 16: more chunk calls than requests
    window = next(l for l in lines if l.get("fact") == "serve_window")
    assert window["prefill_waves"] >= 2 * window["completed"]
    assert window["shed"] == 0 and window["accounted"]


def test_the_logits_check_parts_the_program_from_three_wrong_references():
    """Prefill in chunks through the program the scheduler runs, then decode
    through the pages, against the reference's full forward, on logits and
    on one layer's attention output: the float32 tiny program within 1e-4
    of either scale (the order of its sums); the reference with fp8 weights,
    with the indexer switched off and with half the keys kept far outside
    it."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "logits_check_keye_vl.py"),
         "--rehearsal", "--workload", TINY, "--seeds", f"5,{2 ** 31 + 11}",
         "--steps", "32", "--tolerance", "1e-4", "--attention-tolerance",
         "1e-4"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["holds"] is True
    wrong = last["wrong_mean_diff_over_scale"]
    assert set(wrong) == {"fp8_reference", "no_indexer_reference",
                          "topk_1024_reference"}
    assert last["program_mean_diff_over_scale"] < 1e-4 < 5e-2 \
        < min(wrong.values())
    assert last["program_attention_diff_over_scale"] < 1e-4 < 1e-2 \
        < min(last["wrong_attention_diff_over_scale"].values())
    assert last["program_served_gap_ulps"] == 0
    assert lines[0]["state_kinds"] == "paged_kv+paged_index"
    assert lines[0]["chunk"] == 16 and max(lines[0]["lengths"]) > 3 * 16


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    cell = mf.load_cell(manifest, CELL)
    listed = {m["name"] for m in cell.per_layer}
    for name in NEW:
        entry = manifest["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL]
        assert mf.read_named("metrics", name)["reader"] in (
            "span_need", "scope_device", "scope_roofline", "ring_stat")
    assert set(NEW) | set(APPENDED) <= listed
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if m["name"].rsplit(".", 1)[-1] in (
                    "lfm2", "brumby", "ling", "nemotron", "gigachat",
                    "granite", "train"):
                assert CELL not in m.get("workloads", [CELL + "!"])
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def counters(steps, live, hit, context, with_counters=True):
    """What `steps` decode steps of six layers report with `live` slots of
    `context` cached tokens that hit `hit` experts a layer."""
    if not with_counters:
        return {}
    kept = min(context, 2048)
    return {"moe_routed_pairs": steps * LAYERS * live * 8,
            "moe_held_pairs": steps * LAYERS * live * 8,
            "moe_load_max": steps * LAYERS * 2,
            "moe_load_mean": steps * LAYERS * live * 8 / 128,
            "moe_experts_hit": steps * LAYERS * hit,
            "moe_experts_held": steps * LAYERS * 128,
            "sparse_keys_live": steps * LAYERS * live * context,
            "sparse_keys_kept": steps * LAYERS * live * kept,
            "indexer_cache_bytes_read": steps * LAYERS * live * context * 128.0,
            "kv_bytes_gathered": steps * LAYERS * live * kept * 2048.0}


def chunk_counters(tokens, context, with_counters=True):
    if not with_counters:
        return {}
    at = [context + i for i in range(tokens)]
    return {"moe_held_pairs": LAYERS * 8 * tokens,
            "moe_rows_computed": LAYERS * 8 * tokens,
            "moe_rows_static": LAYERS * 8 * 2048,
            "sparse_keys_live": LAYERS * sum(t + 1 for t in at),
            "sparse_keys_kept": LAYERS * sum(min(t + 1, 2048) for t in at)}


def traced_serving(monkeypatch, with_counters=True):
    """The window's run (profiler off): two requests in three and two chunks
    and two decode windows; then a traced run of 2 s from 20 000 ms whose
    clock in the trace is SKEW ahead: one chunk of 2048 tokens at a context
    of 8192, one decode window of two steps with 6 live slots of 9000 cached
    tokens that hit 41 experts a layer."""
    r = Ring()
    win = r.add("serve/run", 1000, 5000, requests=2)
    for i, (t, n, started) in enumerate([(1100, 2048, 1), (1200, 2048, 0),
                                         (1300, 904, 0), (1400, 2048, 1),
                                         (1500, 2047, 0)]):
        r.add("serve/admit", t, t + 60, parent=win.id, wave=i + 1, requests=1,
              requests_started=started, prompt_tokens=n, padded_tokens=2048)
    r.add("serve/decode/window_sync", 3000, 3010, parent=win.id, window=1,
          steps=4, **counters(4, 8, 52, 5000, with_counters))
    r.add("serve/decode/window_sync", 3100, 3110, parent=win.id, window=2,
          steps=2, **counters(2, 3, 22, 12000, with_counters))
    t0 = 20_000
    root = r.add("serve/run", t0, t0 + 2000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 400, parent=root.id, wave=1,
              requests=1, requests_started=0, prompt_tokens=2048,
              padded_tokens=2048, chunk_index=4, chunks_of_request=6,
              context_before=8192)
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 350, parent=a.id,
          **chunk_counters(2048, 8192, with_counters))
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 1200 + 20 * k, t0 + 1202 + 20 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 1240, t0 + 1250, parent=root.id,
          window=1, steps=2, **counters(2, 6, 41, 9000, with_counters))
    r.install(monkeypatch)

    def at(ms):
        return int((t0 + ms) * MS) + SKEW
    # the chunk: 70 ms of device time; a step: 9 ms, 2 of them the indexers'
    # scores and selection, 1.5 the gather and attention over the kept keys,
    # 3 the expert kernel
    ops = [Op("fusion.1", at(160), at(230)),
           Op("fusion.3", at(1201), at(1203.5)),
           Op("ff_moe_step.9", at(1203.5), at(1206.5)),
           Op("sort.5", at(1206.5), at(1208.5)),
           Op("gather.7", at(1208.5), at(1210)),
           Op("fusion.3", at(1221), at(1223.5)),
           Op("ff_moe_step.9", at(1223.5), at(1226.5)),
           Op("sort.5", at(1226.5), at(1228.5)),
           Op("gather.7", at(1228.5), at(1230))]
    host = [Op("bench/traced_run", at(-5), at(2000))]
    return Run(cell(), trace=Trace({0: ops}, host), window=(at(0), at(2000)))


def test_kept_share_chunks_and_expert_shares_from_the_windows_ring(monkeypatch):
    run = traced_serving(monkeypatch)
    # 8 slots of 5000 in four steps, 3 of 12000 in two: 2048 kept of each
    got = read(run, "sparse_keys_kept_share.decode.keye")
    assert got == pytest.approx(100 * (4 * 8 + 2 * 3) * 2048
                                / (4 * 8 * 5000 + 2 * 3 * 12000))
    # five chunks, two requests started
    assert read(run, "prefill_chunks_per_request.keye") == pytest.approx(2.5)
    assert read(run, "prefill_useful_token_share") == pytest.approx(
        100 * (3 * 2048 + 904 + 2047) / (5 * 2048))
    assert read(run, "moe_experts_hit_share.decode.keye") == pytest.approx(
        100 * (4 * 52 + 2 * 22) / (6 * 128))
    assert read(run, "moe_held_pair_share.decode.keye") == 100.0
    # the curve of a whole holder of 128 at top-8
    assert [round(128 * (1 - (15 / 16) ** n)) for n in (3, 6, 8, 16)] \
        == [23, 41, 52, 82]


def test_shares_of_the_peaks_from_the_traced_runs_spans(monkeypatch):
    run = traced_serving(monkeypatch)
    c = run.cell
    # one decode window of two steps, 9 ms of device time each
    got = read(run, "decode_step_hbm_roofline.keye")
    need = flops.decode_step_need(c.config, c.system, c.traffic, {
        k: v / 2 for k, v in counters(2, 6, 41, 9000).items()})
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 9e-3)
    assert 40 < got < 55
    assert 0.6 < LAYERS * 41 * EXPERT / need["bytes"] < 0.8
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["per"] == 2
    assert note["measured_ms"] == pytest.approx(9.0)
    # one chunk, 70 ms of device time: the need counts the kept keys
    got = read(run, "prefill_mfu.keye")
    need = flops.prefill_chunk_need(c.config, c.system, c.traffic,
                                    chunk_counters(2048, 8192))
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.07)
    assert 10 < got < 20 and run.notes[-1]["bound"] == "compute"


def test_the_three_scopes_shares_of_their_rooflines(monkeypatch):
    """readers/scope_roofline.py over the decode program: the operations
    under `ff_sparse_index`, under `ff_sparse_attend` and under
    `ff_moe_experts`, per decode step, the window's counters over its
    `steps`."""
    from flexflow_tpu import attribution

    run = traced_serving(monkeypatch)
    asked = []

    def under(name, scope):
        asked.append((name, scope))
        return {("serve/decode", "ff_sparse_index"): [{"sort.5"}],
                ("serve/decode", "ff_sparse_attend"): [{"gather.7"}],
                ("serve/decode", "ff_moe_experts"): [{"ff_moe_step.9"}]
                }.get((name, scope), [])

    monkeypatch.setattr(attribution, "instructions_under", under)
    got = read(run, "sparse_index_hbm_roofline.decode.keye")
    # 6 layers x 6 slots x 9000 cached keys of 128 B against 2 ms
    assert got == pytest.approx(100 * LAYERS * 6 * 9000 * 128 / 819e9 / 2e-3)
    assert run.notes[-1]["scope"] == "ff_sparse_index"
    got = read(run, "sparse_attend_hbm_roofline.decode.keye")
    # 6 layers x 6 slots x 2048 kept keys of 2048 B against 1.5 ms
    assert got == pytest.approx(
        100 * LAYERS * 6 * 2048 * 2048 / 819e9 / 1.5e-3)
    assert 10 < got < 15
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["events"] == 2 \
        and note["units"] == 2 and note["scope"] == "ff_sparse_attend"
    assert ("serve/decode", "ff_sparse_index") in asked
    got = read(run, "moe_experts_roofline.decode.keye")
    # 6 layers x 41 hit experts of 9 437 184 B against 3 ms
    assert got == pytest.approx(100 * LAYERS * 41 * EXPERT / 819e9 / 3e-3)
    assert 90 < got < 100 and run.notes[-1]["scope"] == "ff_moe_experts"
    # a program without the scopes in it, and one from before the function
    # (the parent commit): nothing to read
    monkeypatch.setattr(attribution, "instructions_under", lambda n, s: [set()])
    assert read(run, "sparse_attend_hbm_roofline.decode.keye") is None
    monkeypatch.delattr(attribution, "instructions_under")
    assert read(run, "sparse_index_hbm_roofline.decode.keye") is None
    assert read(run, "moe_experts_roofline.decode.keye") is None


def test_nothing_to_read_is_none(monkeypatch):
    """A program without the counters (the parent commit): every metric that
    reads them is left out, none raises."""
    run = traced_serving(monkeypatch, with_counters=False)
    for name in ("sparse_keys_kept_share.decode.keye",
                 "moe_experts_hit_share.decode.keye",
                 "decode_step_hbm_roofline.keye", "prefill_mfu.keye",
                 "sparse_index_hbm_roofline.decode.keye",
                 "sparse_attend_hbm_roofline.decode.keye",
                 "moe_experts_roofline.decode.keye"):
        assert read(run, name) is None, name
