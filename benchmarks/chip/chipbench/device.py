"""The chip: presence check, peaks, compile clock, peak memory."""

from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Any, Dict, List

from chipbench import BENCH_DIR


class NoChip(SystemExit):
    """Raised (exit code 2) when JAX finds no TPU or too few chips."""

    def __init__(self, msg: str):
        print(msg, file=sys.stderr, flush=True)
        super().__init__(2)


def require_tpu(count: int) -> List[Any]:
    """The first ``count`` TPU devices; :class:`NoChip` otherwise.  The
    benchmark never falls back to another platform."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"chipbench: needs a TPU, JAX found "
                     f"{devs[0].platform if devs else 'no device'!r}; no result")
    if len(devs) < count:
        raise NoChip(f"chipbench: needs {count} TPU chips, JAX found "
                     f"{len(devs)}; no result")
    return devs[:count]


def peaks(device_kind: str, path: Path = BENCH_DIR / "peaks.json") -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


class CompileClock:
    """Backend compiles, counted and timed while the clock is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)


def describe(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
