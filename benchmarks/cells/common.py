"""What the cell kinds share: the run's context. The model itself comes from
the configuration's family (families/<family>.py)."""

from __future__ import annotations

import contextlib
import dataclasses
import time

from harness.facts import T_PROCESS_START, emit

# a served token that is not the reference argmax must lie within this many
# bf16 ulps, at the logits' scale, of the reference maximum (chip_smoke.py's
# rule: the system computes in bf16, the reference in f32, so near-ties flip)
NEAR_TIE_ULPS = 8.0


@dataclasses.dataclass
class Ctx:
    cell: object
    seed: int
    seconds: float
    trace: bool
    trace_dir: str
    counter: object          # harness.facts.CompileCounter
    peaks: dict = None       # harness/peaks.json's row for this chip; None in a rehearsal
    trace_window: dict = dataclasses.field(default_factory=dict)

    @property
    def seed32(self) -> int:
        """--seed folded into what a PRNG key and FFConfig.seed hold."""
        return self.seed % (2 ** 31 - 1)

    def span(self, name: str):
        """The benchmark's own host span, on the profiler's clock."""
        import jax

        return jax.profiler.TraceAnnotation(f"bench/{name}")

    @contextlib.contextmanager
    def traced(self):
        """The traced window of a --trace 1 run."""
        import jax

        # the Python call tracer off: it slows the host loop that the window
        # is about and fills the trace; TraceAnnotation spans stay (host
        # tracer), and so does everything on the device
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(self.trace_dir, profiler_options=options):
            yield

    def since_start(self) -> float:
        return time.perf_counter() - T_PROCESS_START


def no_compile_in_window(ctx: Ctx, before: int, what: str) -> bool:
    n = ctx.counter.requests - before
    emit(fact="compiles_in_window", window=what, compile_requests=n)
    return n == 0
