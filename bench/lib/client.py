"""The client: one thread that sends requests to the server and stamps
what it sees.

Open loop: a request is submitted once it is due, whatever the server is
doing.  Closed loop: each of ``clients`` callers sends its next request as
soon as its last one has finished.  Between submissions the client calls
``server.step()``; after each step it stamps every token that step
delivered with the host clock (the engine has synced each token to the
host, so the device is done with it).  Requests are timed from when they
were due.  Requests of the window are drained after it closes, for up to
``drain_s`` more seconds; one that has not finished by then has failed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.lib.traffic import Request, prompt_tokens


@dataclasses.dataclass
class Record:
    req: Request
    due: float  # seconds from the window's start
    submit: float = float("nan")
    admit_step: int = -1
    stamps: list[float] = dataclasses.field(default_factory=list)
    handle: object = None
    done: bool = False


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    admitted: list[int]  # record indices admitted (prefilled) in this step
    prefill_lens: list[int]
    decode_lens: list[int]  # context length of each slot the decode served


@dataclasses.dataclass
class Window:
    seconds: float
    records: list[Record]
    steps: list[Step]
    closed_at: float  # clock at the window's close, from its start
    drained_at: float
    lateness: list[float]  # submit - due for each submitted request


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_window(server, reqs: list[Request], *, seconds: float, vocab: int, seed: int,
               clients: int | None = None, drain_s: float = 60.0,
               annotate: bool = False) -> Window:
    """Serve ``reqs`` over a window of ``seconds``.  ``clients`` set: a
    closed loop over the pool ``reqs``; otherwise ``reqs`` are due at
    ``Request.due``."""
    prompts = {}

    def prompt(r: Request) -> np.ndarray:
        if r.rid not in prompts:
            prompts[r.rid] = prompt_tokens(r, vocab, seed)
        return prompts[r.rid]

    records: list[Record] = []
    inflight: list[int] = []
    queued: list[int] = []
    steps: list[Step] = []
    lateness: list[float] = []
    pool = iter(reqs)
    open_next = 0
    clock = time.perf_counter
    t_start = clock()

    def submit(r: Request, due: float) -> None:
        with _span("client.submit", annotate):
            rec = Record(r, due)
            p = prompt(r)
            rec.submit = clock() - t_start
            rec.handle = server.submit(r.rid, p, r.n_out)
            lateness.append(rec.submit - due)
            records.append(rec)
            inflight.append(len(records) - 1)
            queued.append(len(records) - 1)

    if clients:
        for _ in range(clients):
            submit(next(pool), 0.0)
    while True:
        now = clock() - t_start
        if not clients:
            while open_next < len(reqs) and reqs[open_next].due <= now:
                submit(reqs[open_next], reqs[open_next].due)
                open_next += 1
        if not inflight:
            if clients or open_next >= len(reqs):
                break
            with _span("client.wait", annotate):
                time.sleep(max(0.0, reqs[open_next].due - (clock() - t_start)))
            continue
        if now > seconds + drain_s:
            break
        seen = {i: len(records[i].stamps) for i in inflight}
        was_queued = set(queued)
        t0 = clock() - t_start
        with _span("engine.step", annotate):
            server.step()
        t1 = clock() - t_start
        with _span("client.record", annotate):
            admitted, decode_lens, finished = [], [], []
            for i in inflight:
                rec = records[i]
                toks = server.tokens(rec.handle)
                new = len(toks) - seen[i]
                rec.stamps.extend([t1] * new)
                if i in was_queued and server.admitted(rec.handle):
                    admitted.append(i)
                    rec.admit_step = len(steps)
                    new -= 1  # the prefill's token
                if new > 0:
                    decode_lens.append(rec.req.prompt_len + len(toks) - 1)
                if len(toks) >= rec.req.n_out:
                    rec.done = True
                    finished.append(i)
            steps.append(Step(t0, t1, admitted, [records[i].req.prompt_len for i in admitted],
                              decode_lens))
            adm = set(admitted)
            queued[:] = [i for i in queued if i not in adm]
            inflight[:] = [i for i in inflight if not records[i].done]
            if clients and t1 < seconds:
                for _ in finished:
                    submit(next(pool), clock() - t_start)
    end = clock() - t_start
    return Window(seconds, records, steps, min(end, seconds), end, lateness)
