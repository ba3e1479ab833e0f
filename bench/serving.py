"""The open-loop load generator around ``repro.serve.Server``.

One harness thread: each turn submits every request that is due, then runs
one ``Server.step()``; when nothing is queued it sleeps until the next due
time.  Latency is taken from each request's *scheduled* arrival, so a stall
that delays later submissions counts against them, and how late the
generator submitted is reported beside it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any

import jax
import numpy as np

from repro.core.refine import eps_to_budget
from repro.serve.scheduler import pad_size


class Recording:
    """Mixin for a servable: keeps each batch's raw outputs for the check,
    times its host calls (``phase_s``, per step) and, in a traced run,
    names them in the profiler's trace.

    ``Server`` unpacks a batch's stage-1 outputs first and its refined
    outputs second, so ``recorded`` holds one or two entries per step.
    """

    annotate = False

    @contextlib.contextmanager
    def _annotation(self, name: str):
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(name) if self.annotate
              else contextlib.nullcontext()):
            try:
                yield
            finally:
                if not hasattr(self, "phase_s"):
                    self.phase_s = {}
                self.phase_s[name] = (self.phase_s.get(name, 0.0)
                                      + time.perf_counter() - t0)

    def take_recorded(self) -> list:
        out, self.recorded = getattr(self, "recorded", []), []
        return out

    def take_phases(self) -> dict:
        out, self.phase_s = getattr(self, "phase_s", {}), {}
        return out

    def pad_batch(self, payloads, batch):
        with self._annotation("host.pad_batch"):
            return super().pad_batch(payloads, batch)

    def run(self, prepared, batch_payload, *, refine_budget):
        with self._annotation(f"host.dispatch.budget{refine_budget}"):
            return super().run(prepared, batch_payload,
                               refine_budget=refine_budget)

    def unpack(self, outputs, n):
        if not hasattr(self, "recorded"):
            self.recorded = []
        self.recorded.append(outputs)
        with self._annotation("host.unpack"):
            return super().unpack(outputs, n)

    def error_bounds(self, stage1_out, n):
        with self._annotation("host.error_bounds"):
            return super().error_bounds(stage1_out, n)

    def accuracy_proxy(self, stage1_out, refined_out, n):
        with self._annotation("host.accuracy_proxy"):
            return super().accuracy_proxy(stage1_out, refined_out, n)


class MarkingClock:
    """``perf_counter`` that remembers its last reading: right after
    ``Server.submit`` that reading is the request's ``arrival_t``."""

    def __init__(self):
        self.last = time.perf_counter()

    def __call__(self) -> float:
        self.last = time.perf_counter()
        return self.last


@dataclasses.dataclass
class Outcome:
    """What happened to one scheduled request (absolute perf_counter s)."""

    due: float
    submitted: float = math.nan
    rid: int = -1
    stage1_at: float = math.nan
    final_at: float = math.nan
    eps: float = math.nan
    refined: bool = False
    escalated: bool = False
    skipped: bool = False
    accuracy_met: bool | None = None
    batch: int = -1          # index of the batch that gave the first answer
    row: int = -1            # row of this request in that batch
    final_batch: int = -1    # batch and row of the refined answer (the
    final_row: int = -1      # re-execution's, for an escalated request)

    @property
    def answered(self) -> bool:
        return not math.isnan(self.final_at)


@dataclasses.dataclass
class Batch:
    """One executed batch: the grant it ran under and its raw outputs."""

    rids: list
    n: int
    padded: int
    eps: float
    refine_budget: int
    reexecution: bool
    outputs: list            # [stage-1 outputs, refined outputs?]
    spans: dict | None = None


def _span_summary(root) -> dict:
    """Durations (s) a per-layer reader needs from one ``serve.batch`` tree."""
    out = {"batch_s": root.duration_s, "waits_s": [], "stage1_s": 0.0,
           "stage2_s": 0.0}
    for child in root.children:
        if child.name == "batcher.wait":
            out["waits_s"].append(child.duration_s)
        elif child.name == "stage1":
            out["stage1_s"] += child.duration_s
        elif child.name == "stage2.refine":
            out["stage2_s"] += child.duration_s
    return out


def drive(
    server, kind: str, servable, due: np.ndarray, payloads: list,
    *, deadline_s: float, max_error: float | None, seconds: float,
    drain_s: float, clock: MarkingClock,
    annotate: bool = False,
):
    """Run the open loop over ``due`` (offsets from now, s).

    Returns (outcomes, batches, t0, t_close, t_end, late_s, pauses); each
    batch keeps its raw outputs for the check, and ``pauses`` holds the
    longest ``Server.step`` (s), when it began (s into the window) and the
    servable's host calls inside it (s), and the longest overshoot of a
    sleep (s).
    """
    tracer = server.tracer
    outcomes = [Outcome(due=float(d)) for d in due]
    by_rid: dict[int, int] = {}
    batches: list[Batch] = []
    late = []
    pauses = {"step_s": 0.0, "step_at_s": 0.0, "step_phases": {},
              "oversleep_s": 0.0}
    t0 = clock() + 0.05
    for o in outcomes:
        o.due += t0
    t_close = t0 + seconds
    t_limit = t_close + drain_s
    i, n = 0, len(outcomes)
    step_note = (jax.profiler.TraceAnnotation if annotate
                 else lambda _name: contextlib.nullcontext())
    while True:
        now = clock()
        if now > t_limit:
            break
        if i < n and outcomes[i].due <= now:
            with step_note("host.submit"):
                while i < n and outcomes[i].due <= now:
                    o = outcomes[i]
                    o.rid = server.submit(kind, payloads[i], deadline_s,
                                          max_error=max_error)
                    o.submitted = clock.last
                    late.append(o.submitted - o.due)
                    by_rid[o.rid] = i
                    i += 1
        if len(server.batcher):
            servable.take_phases()
            t_step = time.perf_counter()
            with step_note("host.step"):
                responses = server.step()
            took = time.perf_counter() - t_step
            phases = servable.take_phases()
            if took > pauses["step_s"]:
                pauses.update(step_s=took, step_at_s=t_step - t0,
                              step_phases=phases)
            recorded = servable.take_recorded()
            if not responses:
                continue
            b = len(batches)
            idx = [by_rid[r.rid] for r in responses]
            spans = None
            if tracer.enabled:
                spans = _span_summary(tracer.traces()[-1])
                tracer.reset()
            first = responses[0]
            batches.append(Batch(
                rids=[r.rid for r in responses], n=len(responses),
                padded=pad_size(len(responses), server.batcher.pad_sizes),
                eps=first.eps_granted,
                refine_budget=_budget(servable, first),
                reexecution=first.reexecuted,
                outputs=recorded,
                spans=spans,
            ))
            for row, (j, resp) in enumerate(zip(idx, responses)):
                o = outcomes[j]
                if resp.reexecuted:
                    # Second answer of an escalated request: it is final
                    # when the step that made it returned.
                    o.final_at = clock()
                    o.eps = resp.eps_granted
                    o.refined = resp.refined is not None
                    o.final_batch, o.final_row = b, row
                    continue
                o.batch, o.row = b, row
                o.stage1_at = o.submitted + resp.stage1_latency_s
                o.eps = resp.eps_granted
                o.refined = resp.refined is not None
                if o.refined:
                    o.final_batch, o.final_row = b, row
                o.escalated = resp.escalated
                o.skipped = resp.refine_skipped
                o.accuracy_met = resp.accuracy_met
                if not resp.escalated:
                    o.final_at = o.submitted + resp.total_latency_s
            continue
        if i >= n:
            break
        wait = outcomes[i].due - clock()
        if wait > 0:
            with step_note("host.idle"):
                time.sleep(wait)
            pauses["oversleep_s"] = max(pauses["oversleep_s"],
                                        clock() - outcomes[i].due)
    return (outcomes, batches, t0, t_close, clock(), np.asarray(late),
            pauses)


def _budget(servable, resp) -> int:
    if resp.refined is None:
        return 0
    return eps_to_budget(servable.n_points, resp.eps_granted)


def result_lines(o: list[Outcome]) -> dict[str, Any]:
    """Counts that the harness prints on an earlier line."""
    hist: dict[str, int] = {}
    for x in o:
        if not math.isnan(x.eps):
            key = f"{x.eps:g}"
            hist[key] = hist.get(key, 0) + 1
    return {
        "granted_eps": hist,
        "escalated": sum(x.escalated for x in o),
        "skipped": sum(x.skipped for x in o),
        "refined": sum(x.refined for x in o),
    }
