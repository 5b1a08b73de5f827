"""The `mellum` family in the benchmark: its tiny cell through
rehearse_mellum.py (the serving cell's whole control flow on the CPU backend,
prompts prefilled in chunks over the slot's pages and its ring; the family's
own manifest rehearsal_mellum.json, since rehearsal.json is the benchmark's
and not a model PR's to edit), the metrics this family brought, read from a
hand-made ring and a hand-made reduced trace (the windowed layers' share of
the pages a step reads, the pools' share of one extent through
readers/named_scope.py, the two shares of a peak through readers/span_need.py,
the scopes' shares of their rooflines through readers/scope_roofline.py, the
windowed layers' device time through readers/named_scope.py), and how tight
the comparison is: the logits check with its three wrong references
(logits_check_mellum.py) at the tiny size. Nothing here times anything. New
entries of the manifest are found by membership: nothing here asserts that
an entry is the last, or how many there are."""

import json
import subprocess
import sys

import pytest

from test_granitemoehybrid_rehearsal import (BENCH, CPU_ENV, MS, ROOT, SKEW,
                                             Ring, Run, read)

from harness import flops_mellum as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402

CONFIG = "Mellum2-12B-A2.5B-Instruct"
CELL = CONFIG + ".serve-longprompt"
TINY = "mellum-tiny.serve"
NEW = ["prefill_mfu.mellum", "decode_step_hbm_roofline.mellum",
       "window_attend_hbm_roofline.decode.mellum",
       "full_attend_hbm_roofline.decode.mellum", "chunk_attend_mfu.mellum",
       "window_kv_read_share.decode.mellum",
       "kv_pool_share_of_one_extent.mellum",
       "wave_window_attend_device_ms.mellum",
       "decode_window_attend_device_ms_per_step.mellum",
       "moe_experts_hit_share.decode.mellum",
       "moe_held_pair_share.decode.mellum",
       "moe_expert_load_max_over_mean.decode.mellum",
       "moe_experts_roofline.decode.mellum",
       "prefill_chunks_per_request.mellum"]
APPENDED = ["moe_rows_computed_share.prefill", "wave_experts_device_ms",
            "decode_experts_device_ms_per_step", "wave_attention_device_ms",
            "decode_attention_device_ms_per_step",
            "prefill_useful_token_share", "queue_wait_p95_ms"]
REHEARSAL = "rehearsal_mellum.json"
EXPERT = 12386304               # one expert's three matrices, bf16 bytes
ROW = 2048                      # a token's K and V in one layer, bf16 bytes


def built():
    return mf.read_named("configs", CONFIG)["num_hidden_layers"]


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    # the benchmark's own rehearsal manifest is as it was
    assert "mellum" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_mellum.py"), "--workload",
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
    # no recurrent state, no latent, no indexer in this model
    assert not {"wave_mixer_device_ms", "state_commit_ms",
                "latent_cache_read_mb_per_step.decode",
                "sparse_keys_kept_share.decode.keye"} \
        & set(last["would_report"])
    # prompts of 17-80 tokens in chunks of 32: more chunk calls than requests
    window = next(l for l in lines if l.get("fact") == "serve_window")
    assert window["prefill_waves"] > window["completed"]
    assert window["shed"] == 0 and window["accounted"]


def test_the_logits_check_parts_the_program_from_three_wrong_references():
    """Prefill in chunks through the program the scheduler runs, then decode
    through both pools, against the reference's full forward, on logits and
    on one layer of each kind's attention output: the float32 tiny program
    within 1e-4 of either scale (the order of its sums); the reference with
    fp8 weights, with half the window and with plain tables on the full
    layer far outside it."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "logits_check_mellum.py"),
         "--rehearsal", "--workload", TINY, "--seeds", f"5,{2 ** 31 + 11}",
         "--steps", "32", "--tolerance", "1e-4", "--attention-tolerance",
         "1e-4"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:] + done.stdout[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["holds"] is True
    wrong = last["wrong_mean_diff_over_scale"]
    assert set(wrong) == {"fp8_reference", "window_512_reference",
                          "no_yarn_reference"}
    assert last["program_mean_diff_over_scale"] < 1e-4 < 2e-2 \
        < min(wrong.values())
    assert last["program_attention_diff_over_scale"] < 1e-4 < 5e-3 \
        < min(last["wrong_attention_diff_over_scale"].values())
    assert min(last["attention_margin_over_program"].values()) > 1e3
    assert last["program_served_gap_ulps"] == 0
    assert lines[0]["state_kinds"] == "paged_kv+paged_kv_ring"
    # the longest prompt laps the ring
    assert lines[0]["chunk"] == 32 and max(lines[0]["ring_laps"]) > 1.5


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    cell = mf.load_cell(manifest, CELL)
    listed = {m["name"] for m in cell.per_layer}
    for name in NEW:
        entry = manifest["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL]
        assert mf.read_named("metrics", name)["reader"] in (
            "span_need", "scope_roofline", "ring_stat", "named_scope")
    assert set(NEW) | set(APPENDED) <= listed
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if m["name"].rsplit(".", 1)[-1] in (
                    "keye", "lfm2", "brumby", "ling", "nemotron", "gigachat",
                    "granite", "train"):
                assert CELL not in m.get("workloads", [CELL + "!"])
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "serve-longprompt"
    assert len(entry["why"]) <= 200


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def counters(steps, live, hit, context, with_counters=True):
    """What `steps` decode steps report with `live` slots of `context`
    cached tokens that hit `hit` experts a layer: a windowed layer's step
    sees 1024 keys and fetches 65 pages, a full one's the context."""
    if not with_counters:
        return {}
    layers = built()
    window, full = 3 * layers // 4, layers // 4
    return {"moe_routed_pairs": steps * layers * live * 8,
            "moe_held_pairs": steps * layers * live * 8,
            "moe_load_max": steps * layers * 2,
            "moe_load_mean": steps * layers * live * 8 / 64,
            "moe_experts_hit": steps * layers * hit,
            "moe_experts_held": steps * layers * 64,
            "window_keys_seen": steps * window * live * 1024,
            "full_keys_seen": steps * full * live * context,
            "window_kv_bytes_needed": steps * window * live * 1024 * ROW,
            "full_kv_bytes_needed": steps * full * live * context * ROW,
            "window_kv_bytes_streamed": steps * window * live * 65 * 16 * ROW,
            "full_kv_bytes_streamed":
                steps * full * live * -(-context // 16) * 16 * ROW}


def chunk_counters(tokens, context, with_counters=True):
    if not with_counters:
        return {}
    layers = built()
    at = [context + i for i in range(tokens)]
    return {"moe_held_pairs": layers * 8 * tokens,
            "moe_rows_computed": layers * 8 * tokens,
            "moe_rows_static": layers * 8 * 2048,
            "window_keys_seen": 3 * layers // 4 * sum(min(t + 1, 1024)
                                                      for t in at),
            "full_keys_seen": layers // 4 * sum(t + 1 for t in at)}


def traced_serving(monkeypatch, with_counters=True):
    """The window's run (profiler off): two requests in three and two chunks
    and two decode windows; then a traced run of 2 s from 20 000 ms whose
    clock in the trace is SKEW ahead: one chunk of 2048 tokens at a context
    of 6144, one decode window of two steps with 6 live slots of 9000 cached
    tokens that hit 34 experts a layer. The compile span lies before both."""
    r = Ring()
    if with_counters:
        layers = built()
        r.add("serve/compile_serving", 100, 900, slots=16,
              kv_pool_bytes_full=layers // 4 * 16897 * 16 * ROW,
              kv_pool_bytes_window=3 * layers // 4 * 3089 * 16 * ROW,
              kv_pool_bytes_one_extent=layers * 16897 * 16 * ROW,
              window_ring_pages=193)
    win = r.add("serve/run", 1000, 5000, requests=2)
    for i, (t, n, started) in enumerate([(1100, 2048, 1), (1200, 2048, 0),
                                         (1300, 904, 0), (1400, 2048, 1),
                                         (1500, 2047, 0)]):
        r.add("serve/admit", t, t + 60, parent=win.id, wave=i + 1, requests=1,
              requests_started=started, prompt_tokens=n, padded_tokens=2048)
    r.add("serve/decode/window_sync", 3000, 3010, parent=win.id, window=1,
          steps=4, **counters(4, 8, 41, 5000, with_counters))
    r.add("serve/decode/window_sync", 3100, 3110, parent=win.id, window=2,
          steps=2, **counters(2, 3, 20, 12000, with_counters))
    t0 = 20_000
    root = r.add("serve/run", t0, t0 + 2000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 400, parent=root.id, wave=1,
              requests=1, requests_started=0, prompt_tokens=2048,
              padded_tokens=2048, chunk_index=3, chunks_of_request=6,
              context_before=6144)
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 350, parent=a.id,
          **chunk_counters(2048, 6144, with_counters))
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 1200 + 20 * k, t0 + 1202 + 20 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 1240, t0 + 1250, parent=root.id,
          window=1, steps=2, **counters(2, 6, 34, 9000, with_counters))
    r.install(monkeypatch)

    def at(ms):
        return int((t0 + ms) * MS) + SKEW
    # the chunk: 100 ms of device time, 8 of them the windowed layers'
    # attention and 10 the full ones'; a step: 8 ms, 0.5 of them the windowed
    # layers' kernel, 0.5 the full ones', 4 the expert kernel
    ops = [Op("fusion.1", at(160), at(242)),
           Op("ff_sparse_attend_chunk.2", at(242), at(250)),
           Op("ff_sparse_attend_chunk.4", at(250), at(260)),
           Op("fusion.3", at(1201), at(1204)),
           Op("ff_moe_step.9", at(1204), at(1208)),
           Op("ff_sparse_attend_step.5", at(1208), at(1208.5)),
           Op("ff_sparse_attend_step.7", at(1208.5), at(1209)),
           Op("fusion.3", at(1221), at(1224)),
           Op("ff_moe_step.9", at(1224), at(1228)),
           Op("ff_sparse_attend_step.5", at(1228), at(1228.5)),
           Op("ff_sparse_attend_step.7", at(1228.5), at(1229))]
    host = [Op("bench/traced_run", at(-5), at(2000))]
    return Run(cell(), trace=Trace({0: ops}, host), window=(at(0), at(2000)))


SCOPES = {("serve/decode", "ff_window_attend"): [{"ff_sparse_attend_step.5"}],
          ("serve/decode", "ff_full_attend"): [{"ff_sparse_attend_step.7"}],
          ("serve/decode", "ff_moe_experts"): [{"ff_moe_step.9"}],
          ("serve/prefill", "ff_window_attend"):
              [{"ff_sparse_attend_chunk.2"}],
          ("serve/prefill", "ff_bounded_attend"):
              [{"ff_sparse_attend_chunk.2", "ff_sparse_attend_chunk.4"}]}


def test_the_windows_shares_from_the_ring(monkeypatch):
    run = traced_serving(monkeypatch)
    # the windowed layers fetch 65 pages a slot a step; the full ones 313
    # pages (5000 tokens) and 750 (12000): nine layers against three x 9 / 3
    got = read(run, "window_kv_read_share.decode.mellum")
    assert got == pytest.approx(100 * (4 * 8 + 2 * 3) * 65
                                / (4 * 8 * 313 + 2 * 3 * 750))
    # the pools: three full layers and nine rings over twelve full layers
    got = read(run, "kv_pool_share_of_one_extent.mellum")
    assert got == pytest.approx(100 * (16897 + 3 * 3089) / (4 * 16897))
    assert 38 < got < 40
    assert read(run, "prefill_chunks_per_request.mellum") == pytest.approx(2.5)
    assert read(run, "moe_experts_hit_share.decode.mellum") == pytest.approx(
        100 * (4 * 41 + 2 * 20) / (6 * 64))
    assert read(run, "moe_held_pair_share.decode.mellum") == 100.0
    # the curve of a whole holder of 64 at top-8
    assert [round(64 * (1 - (7 / 8) ** n)) for n in (3, 6, 8, 16)] \
        == [21, 35, 42, 56]


def test_shares_of_the_peaks_from_the_traced_runs_spans(monkeypatch):
    run = traced_serving(monkeypatch)
    c = run.cell
    # one decode window of two steps, 8 ms of device time each
    got = read(run, "decode_step_hbm_roofline.mellum")
    need = flops.decode_step_need(c.config, c.system, c.traffic, {
        k: v / 2 for k, v in counters(2, 6, 34, 9000).items()})
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 8e-3)
    assert 50 < got < 100
    assert 0.7 < built() * 34 * EXPERT / need["bytes"] < 0.85
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["per"] == 2
    assert note["measured_ms"] == pytest.approx(8.0)
    # one chunk, 100 ms of device time: the need counts the keys seen
    got = read(run, "prefill_mfu.mellum")
    need = flops.prefill_chunk_need(c.config, c.system, c.traffic,
                                    chunk_counters(2048, 6144))
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.1)
    assert 15 < got < 30 and run.notes[-1]["bound"] == "compute"


def test_the_scopes_shares_of_their_rooflines_and_their_time(monkeypatch):
    """readers/scope_roofline.py and readers/named_scope.py over the two
    programs: the operations under `ff_window_attend`, `ff_full_attend`,
    `ff_bounded_attend` and `ff_moe_experts`."""
    from flexflow_tpu import attribution

    run = traced_serving(monkeypatch)
    monkeypatch.setattr(attribution, "instructions_under",
                        lambda name, scope: SCOPES.get((name, scope), []))
    layers = built()
    window, full = 3 * layers // 4, layers // 4
    got = read(run, "window_attend_hbm_roofline.decode.mellum")
    # the windowed layers x 6 slots x 1024 keys of 2048 B against 0.5 ms
    assert got == pytest.approx(100 * window * 6 * 1024 * ROW / 819e9 / 0.5e-3)
    assert run.notes[-1]["scope"] == "ff_window_attend"
    got = read(run, "full_attend_hbm_roofline.decode.mellum")
    assert got == pytest.approx(100 * full * 6 * 9000 * ROW / 819e9 / 0.5e-3)
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["events"] == 2 \
        and note["units"] == 2 and note["scope"] == "ff_full_attend"
    got = read(run, "moe_experts_roofline.decode.mellum")
    assert got == pytest.approx(100 * layers * 34 * EXPERT / 819e9 / 4e-3)
    # a chunk's attention: both kinds' products over 18 ms
    got = read(run, "chunk_attend_mfu.mellum")
    c = run.cell
    need = flops.chunk_attend_need(c.config, c.system, c.traffic,
                                   chunk_counters(2048, 6144))
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 18e-3)
    assert run.notes[-1]["scope"] == "ff_bounded_attend"
    # the windowed layers' device time a chunk and a step
    assert read(run, "wave_window_attend_device_ms.mellum") \
        == pytest.approx(8.0)
    assert read(run, "decode_window_attend_device_ms_per_step.mellum") \
        == pytest.approx(0.5)
    note = run.notes[-1]
    assert note["scope"] == "ff_window_attend" and note["units"] == 2 \
        and note["events"] == 2 and note["per"] == "steps"
    # a program without the scopes in it, and one from before the function
    # (the parent commit): nothing to read
    monkeypatch.setattr(attribution, "instructions_under", lambda n, s: [set()])
    assert read(run, "window_attend_hbm_roofline.decode.mellum") is None
    assert read(run, "wave_window_attend_device_ms.mellum") is None
    monkeypatch.delattr(attribution, "instructions_under")
    assert read(run, "full_attend_hbm_roofline.decode.mellum") is None
    assert read(run, "decode_window_attend_device_ms_per_step.mellum") is None


def test_nothing_to_read_is_none(monkeypatch):
    """A program without the counters and the compile span's facts (the
    parent commit): every metric that reads them is left out, none raises."""
    from flexflow_tpu import attribution

    run = traced_serving(monkeypatch, with_counters=False)
    monkeypatch.setattr(attribution, "instructions_under",
                        lambda name, scope: SCOPES.get((name, scope), []))
    for name in ("window_kv_read_share.decode.mellum",
                 "kv_pool_share_of_one_extent.mellum",
                 "moe_experts_hit_share.decode.mellum",
                 "decode_step_hbm_roofline.mellum", "prefill_mfu.mellum",
                 "window_attend_hbm_roofline.decode.mellum",
                 "full_attend_hbm_roofline.decode.mellum",
                 "chunk_attend_mfu.mellum",
                 "moe_experts_roofline.decode.mellum"):
        assert read(run, name) is None, name
