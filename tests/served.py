"""The hand-driven serving harness of the family tests (`test_<family>.py`).

`Served` drives `engine.prefill` / `engine.decode_step` and the cache by hand,
keeps each slot's tokens, and holds every logits row that comes out against a
reference's full forward over the slot's tokens. What differs by family is
handed in and nothing else: the pair of input builders, the reference, and
what a wave's and a step's `STATS_KEY` counters must say.

A `model_config` PR writes its parity test against this class; it is not
for `tests/benchmark/`, which may import nothing from here.
"""

import jax.numpy as jnp
import numpy as np

from flexflow_tpu import telemetry as tel
from flexflow_tpu.ops.registry import STATS_KEY


def off_by(got, want):
    """The largest difference over the reference's largest magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    return float(np.abs(got - want).max()) / float(np.abs(want).max())


class Served:
    """`reference(ids)`: float32 logits `[rows, n, vocab]` of the plain
    forward over `ids [rows, n]`. `prompt_inputs(ids, lengths)` /
    `step_inputs(tokens, state)`: the engine's input builders.
    `wave_stats(served, stats, prompts)` / `step_stats(served, stats)`
    assert on the counters a wave / a step reports. `scheduler_path`: the
    prefill the scheduler runs (first tokens on the device; the wave writes
    its own slots where the cache says so) instead of the full-logits one:
    a wave then has no rows to check."""

    def __init__(self, eng, reference, prompt_inputs, step_inputs, rtol,
                 wave_stats=None, step_stats=None, scheduler_path=False):
        self.eng, self.reference, self.rtol = eng, reference, rtol
        self.prompt_inputs, self.step_inputs = prompt_inputs, step_inputs
        self.wave_stats, self.step_stats = wave_stats, step_stats
        self.scheduler_path = scheduler_path
        self.slots, self.seq = eng.prefill_model.input_tensors[0].spec.shape
        self.seqs = {}
        self.checked = 0
        self._padding_shown = False

    def check(self, rows):
        """{slot: logits row after the slot's last token} against the
        reference's row there. One reference call for all of them, every
        slot's tokens padded with zeros to the wave's length: the models are
        causal, so a row does not depend on what lies to its right, and one
        shape is traced where each slot's own length would be a new one
        (0.8 s a row). The first row of a harness is also held against the
        reference over the slot's own tokens and no more, to a tenth of the
        tolerance: float32 rounding parts the two, 1.4e-6 at most over the
        five families' rows against the 1e-4 they are held to."""
        ids = np.zeros((self.slots, self.seq), np.int32)
        for slot in rows:
            ids[slot, :len(self.seqs[slot])] = self.seqs[slot]
        want = np.asarray(self.reference(ids))
        for slot, row in rows.items():
            n = len(self.seqs[slot])
            if not self._padding_shown:
                alone = self.reference(np.asarray([self.seqs[slot]], np.int32))
                assert off_by(want[slot, n - 1], np.asarray(alone)[0, -1]) \
                    <= self.rtol / 10
                self._padding_shown = True
            off = off_by(row, want[slot, n - 1])
            assert off <= self.rtol, (slot, n, off)
            self.checked += 1

    def wave(self, prompts):
        """Prefill {slot: prompt} as one padded wave; the other slots sit
        it out (length 0)."""
        eng, kv = self.eng, self.eng.kv
        ids = np.zeros((self.slots, self.seq), np.int32)
        lengths = np.zeros(self.slots, np.int32)
        for slot, prompt in prompts.items():
            kv.admit(slot, len(prompt), len(prompt) + 16)
            ids[slot, :len(prompt)] = prompt
            lengths[slot] = len(prompt)
            self.seqs[slot] = list(prompt)
        kv.push()
        inputs = self.prompt_inputs(ids, lengths)
        if self.scheduler_path:
            first, kv_state = eng.prefill_first_tokens(eng.params, inputs,
                                                       lengths)
            if kv.writes_state_in_place:
                assert not set(kv_state) & set(kv.recurrent)
            first = np.asarray(first)
        else:
            logits, kv_state = eng.prefill(eng.params, inputs)
        stats = kv_state.pop(STATS_KEY, None)
        if self.wave_stats is not None:
            self.wave_stats(self, stats, prompts)
        kv.commit_prefill(kv_state, np.arange(self.slots, dtype=np.int32),
                          lengths)
        if not self.scheduler_path:
            logits = np.asarray(logits)
            rows = {slot: logits[slot, len(prompt) - 1]
                    for slot, prompt in prompts.items()}
            self.check(rows)
            first = {slot: int(row.argmax()) for slot, row in rows.items()}
        for slot in prompts:
            self.seqs[slot].append(int(first[slot]))

    def decode(self, steps):
        eng, kv = self.eng, self.eng.kv
        for _ in range(steps):
            nxt = np.zeros((self.slots, 1), np.int32)
            for slot, seq in self.seqs.items():
                nxt[slot, 0] = seq[-1]
            state = kv.state
            logits, state = eng.decode_step(
                eng.params, state, self.step_inputs(jnp.asarray(nxt), state))
            stats = state.pop(STATS_KEY)
            if self.step_stats is not None:
                self.step_stats(self, stats)
            kv.adopt(state)
            kv.sync_after(1)
            logits = np.asarray(logits)
            self.check({slot: logits[slot, 0] for slot in self.seqs})
            for slot in self.seqs:
                self.seqs[slot].append(int(logits[slot, 0].argmax()))

    def evict(self, slot):
        self.eng.kv.evict(slot)
        self.eng.kv.push()
        del self.seqs[slot]


def scheduler_reports_the_step_path(eng, reference, prompt_inputs,
                                    step_inputs, vocab, want: dict,
                                    mixers: set):
    """Five requests through `ContinuousBatchingScheduler` on a model with
    Mamba-2 layers: every served token is `reference`'s argmax over the
    request's own tokens, every decode mixer (`mixers`: their names) reports
    its form once in an `ssm/step_path` span whose facts are `want`, and
    `ssm_step_kernel_slots` on the decode spans counts the (layer, live
    slot) pairs that `ssm_state_bytes` counts where the kernel ran, 0 where
    the XLA lines did; a wave reports neither."""
    from flexflow_tpu.serving import ContinuousBatchingScheduler, Request

    tel.ring_clear()
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(0, vocab, n)],
                    max_new_tokens=new, arrival_s=0.0)
            for i, (n, new) in enumerate([(5, 6), (17, 4), (30, 8), (9, 5),
                                          (12, 7)])]
    sched = ContinuousBatchingScheduler(eng, eng.params, prompt_inputs,
                                        step_inputs, eos_id=None)
    sched.run(reqs)
    assert len(sched.completed) == len(reqs)
    for r in reqs:
        logits = np.asarray(reference(
            np.asarray([r.prompt + r.tokens], np.int32)))[0]
        rows = logits[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.tokens)]
        assert (rows.argmax(-1) == np.asarray(r.tokens)).all(), r.rid
    spans = {}
    for sp in tel.ring_spans():
        spans.setdefault(sp.name, []).append(sp.args or {})
    forms = spans["ssm/step_path"]
    assert {a["layer"] for a in forms} == mixers
    assert all({k: v for k, v in a.items() if k != "layer"} == want
               for a in forms)
    # one layer's state a slot: both leaves
    a_slot = eng.kv_spec.state_bytes_per_slot // len(mixers)
    syncs = spans["serve/decode/window_sync"]
    assert syncs and sum(a["steps"] for a in syncs) == sched.decode_steps
    for a in syncs:
        pairs = a["ssm_state_bytes"] / (2 * a_slot)
        assert 0 < pairs <= a["steps"] * len(mixers) * eng.slots \
            and pairs == int(pairs)
        assert a["ssm_step_kernel_slots"] \
            == (pairs if want["path"] == "kernel" else 0)
    assert "ssm_step_kernel_slots" not in spans["serve/prefill/device_wait"][0]
