"""Serving cells: the program's ``PagedServeLoop`` under waves of requests.

Set-up builds the loop at the configuration's sizes, replaces its weights
by the benchmark's own (drawn in one jitted call from the seed; the tree
must match the program's layout leaf for leaf), and warms every prefill
bucket and the decode program with one small call of ``run``.  The window
then sends waves back to back while the clock is under ``seconds``; each
wave is one ``run`` call and every request in it arrives at its start.
The interval ends when the last wave that started inside it finishes.

With ``trace``, the profiler records the window's first wave, or its
first ``TRACE_CAP_SECONDS`` where that wave lasts longer, and the two
jitted programs are wrapped meanwhile to record what each call was asked
to compute (:class:`TracedCalls`).

After the window, and after the program's state is freed, a sample of
the finished requests drawn from the seed is checked against the float32
reference (``chipbench.correct``).
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time
from types import SimpleNamespace
from typing import Any, Dict, List

import numpy as np

from chipbench import REPO_ROOT, correct, device, trace as tr
from chipbench.traffic import Traffic
from chipbench.work import Shapes

TRACE_CAP_SECONDS = 60.0   # a first wave longer than this is traced this long

# configuration-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "attention_bias": "qkv_bias",
          "tie_word_embeddings": "tie_embeddings", "head_dim": "head_dim"}


def program_config(cfg: Dict[str, Any], smoke: bool = False):
    """The program's registered config with the file's values on top;
    every field that differs from the registry is reported on stderr."""
    if str(REPO_ROOT / "src") not in sys.path:
        sys.path.insert(0, str(REPO_ROOT / "src"))
    from repro.configs import get_config, get_smoke_config
    base = (get_smoke_config if smoke else get_config)(cfg["program_arch"])
    want = {f: cfg[k] for k, f in FIELDS.items() if k in cfg}
    changed = {f: (getattr(base, f), v) for f, v in want.items()
               if getattr(base, f) != v}
    if changed:
        print(f"chipbench: {cfg['name']} runs the program's "
              f"{cfg['program_arch']} with {changed} (registry, file)",
              file=sys.stderr)
    return dataclasses.replace(base, **want)


def _shapes(tree) -> Any:
    import jax
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def build(cfg: Dict[str, Any], seed: int, reference, smoke: bool = False):
    """The loop at the configuration's sizes, holding the benchmark's
    weights."""
    from repro.launch.serve import PagedServeLoop
    s = cfg["serve"]
    loop = PagedServeLoop(
        program_config(cfg, smoke), seed=0, scheduler=s["scheduler"],
        num_blocks=s["num_blocks"], block_size=s["block_size"],
        max_context=s["max_context"], concurrency=s["concurrency"],
        decode_steps=s["decode_steps"], prefill_chunk=s["prefill_chunk"])
    layout = _shapes(loop.params)
    loop.params = None
    gc.collect()
    weights = reference.make_weights(cfg, seed)
    if _shapes(weights) != layout:
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter layout")
    loop.params = weights
    return loop, weights


def warm(loop, cfg: Dict[str, Any]) -> None:
    """One ``run`` whose prompts hit every prefill bucket and decode."""
    from repro.launch.serve import Request
    s = cfg["serve"]
    lengths, b = [], 8
    while b < s["prefill_chunk"]:
        lengths.append(b)
        b *= 2
    lengths.append(s["prefill_chunk"])
    loop.run([Request(rid=-1 - i, prompt=np.zeros(n, np.int32),
                      max_new=s["decode_steps"] + 1)
              for i, n in enumerate(lengths)])
    import jax
    jax.block_until_ready(loop.cache)


class TracedCalls:
    """While the trace is on, the loop's two jitted programs go through
    this: each call's asked-for work is recorded (a prefill chunk's start
    and length; a decode dispatch's rows, their fills and the tokens each
    made).  The trace is stopped and the programs are handed back when the
    first wave ends (:meth:`close`), or earlier at the first call after
    ``deadline`` once both programs have run.  The loop reads every result
    back before its next call, so a call boundary is a clean end of the
    trace.
    Reading the arguments costs a few small device reads per call, in the
    traced part only."""

    def __init__(self, loop, deadline: float, stop):
        self.loop, self.deadline, self.stop = loop, deadline, stop
        self.calls: List[Dict[str, Any]] = []
        self.programs = (loop._prefill_step, loop._decode)
        loop._prefill_step = _Proxy(self.programs[0], self, self._prefill)
        loop._decode = _Proxy(self.programs[1], self, self._decode)

    def due(self) -> bool:
        """Past the deadline, and each program traced at least once."""
        if self.loop._decode is self.programs[1]:
            return True
        seen = {c["program"] for c in self.calls}
        if time.perf_counter() < self.deadline or len(seen) < 2:
            return False
        self.close()
        return True

    def close(self) -> None:
        if self.loop._decode is not self.programs[1]:
            self.loop._prefill_step, self.loop._decode = self.programs
            self.stop()

    def _prefill(self, args, out) -> None:
        self.calls.append({"program": "prefill_chunk", "start": int(args[4]),
                           "length": int(args[5])})

    def _decode(self, args, out) -> None:
        fill, mask = np.asarray(args[4]), np.asarray(args[6])
        made = np.asarray(args[7]) - np.asarray(out[4])
        self.calls.append({"program": "serve_step",
                           "rows": [(int(fill[r]), int(made[r]))
                                    for r in np.flatnonzero(mask)]})


class _Proxy:
    def __init__(self, fn, watch: TracedCalls, record):
        self.fn, self.watch, self.record = fn, watch, record

    def __call__(self, *args):
        if self.watch.due():
            return self.fn(*args)
        out = self.fn(*args)
        self.record(args, out)
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


def run(ctx: SimpleNamespace) -> SimpleNamespace:
    """Set-up, window, trace and check of one serving cell.  ``ctx`` has
    ``cfg``, ``traffic``, ``seed``, ``seconds``, ``trace``, ``devices``,
    ``reference``, ``t_start``, ``trace_dir`` and ``smoke``."""
    import jax
    from repro.launch.serve import Request
    cfg = ctx.cfg
    loop, weights = build(cfg, ctx.seed, ctx.reference, ctx.smoke)
    warm(loop, cfg)
    setup_s = time.perf_counter() - ctx.t_start
    traffic = Traffic(ctx.traffic, cfg["vocab_size"], ctx.seed)

    requests: List[Dict[str, Any]] = []
    dispatches: List[Dict[str, Any]] = []
    waves: List[Dict[str, Any]] = []
    watch = None
    paused = [0.0]         # closing the trace is not serving time

    def stop_trace() -> None:
        t = time.perf_counter()
        annotation.__exit__(None, None, None)
        tr.stop()
        paused[0] += time.perf_counter() - t

    clock = device.CompileClock()
    with clock:
        t0 = time.perf_counter()
        w = 0
        while w == 0 or time.perf_counter() - t0 - paused[0] < ctx.seconds:
            if ctx.trace and w == 0:
                tr.start(ctx.trace_dir)
                annotation = jax.profiler.TraceAnnotation(tr.WINDOW)
                annotation.__enter__()
                watch = TracedCalls(loop, t0 + TRACE_CAP_SECONDS, stop_trace)
            wanted = traffic.wave(w)
            reqs = [Request(rid=w * 10_000 + k, prompt=q.prompt,
                            max_new=q.max_new) for k, q in enumerate(wanted)]
            t_w = time.perf_counter()
            for r in reqs:
                r.t_arrive = t_w
            loop.run(reqs)
            t_done = time.perf_counter()
            waves.append({"wave": w, "start": t_w, "end": t_done})
            for r in reqs:
                requests.append({
                    "wave": w, "rid": r.rid, "prompt": r.prompt,
                    "max_new": r.max_new, "generated": list(r.generated or []),
                    "t_arrive": r.t_arrive, "t_admit": r.t_admit,
                    "t_first": r.t_first, "t_finish": r.t_finish,
                    "preemptions": r.preemptions})
            dispatches.extend(dict(d, wave=w) for d in loop.dispatch_log)
            if watch is not None:
                watch.close()          # the trace ends with the first wave
            w += 1
        interval = time.perf_counter() - t0 - paused[0]
    reduced = None
    if watch is not None:
        reduced = tr.reduce(tr.extract(ctx.trace_dir))

    memory_peak = device.peak_bytes(ctx.devices)
    del loop
    gc.collect()
    check = correct.check_served(cfg, ctx.reference, weights, requests,
                                 ctx.seed, traffic,
                                 quant=getattr(ctx, "control", None))
    return SimpleNamespace(
        kind="serve", cfg=cfg, shapes=Shapes.of(cfg), serve=cfg["serve"],
        requests=requests, dispatches=dispatches, waves=waves,
        interval_s=interval, setup_s=setup_s, trace=reduced,
        traced_calls=watch.calls if watch else [], window_compiles=clock.count,
        window_compile_s=clock.seconds, memory_peak_bytes=memory_peak,
        check=check)
