"""The telemetry facade: one registry + one flight recorder + exporters.

`GLOBAL_TELEMETRY` is the process-wide instance, disabled by default just
like `GLOBAL_TRACER` — every instrumentation site in the stack guards with
`if GLOBAL_TELEMETRY.enabled:` so a disabled session pays one attribute
read and a branch, nothing else. Enabling mid-session is legal: instruments
are pre-bound eagerly, so counters simply start moving.

Tracer spans are a registry histogram (`ggrs_span_ms{span}`), so both
exporters carry them like any other instrument; `snapshot()` adds a
`tracer` section, a per-span summary of that histogram, so there is ONE
report (metrics + flight-recorder tail + tracer spans).

On `DesyncDetected` the P2P session calls `write_desync_forensics()`: the
divergent frame, both checksums, the last-N flight-recorder events and the
still-pending predicted inputs land in one JSON dump file, so a desync is
diagnosable after the process is gone.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from .metrics import MetricsRegistry
from .recorder import DEFAULT_CAPACITY, FlightRecorder, jsonable


class Telemetry:
    # hard cap on forensics dumps per Telemetry instance: a desync storm
    # (every comparison interval re-detects) must not flood the disk
    MAX_FORENSICS_DUMPS = 32

    def __init__(
        self,
        enabled: bool = False,
        recorder_capacity: int = DEFAULT_CAPACITY,
        dump_dir: Optional[str] = None,
    ):
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.recorder = FlightRecorder(recorder_capacity)
        # None -> resolved at dump time from $GGRS_OBS_DUMP_DIR, else cwd
        self.dump_dir = dump_dir
        self._dumps_written = 0

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------

    def record(self, kind: str, frame: int = -1, **data: Any) -> None:
        """Flight-recorder entry point; no-op when disabled."""
        if self.enabled:
            self.recorder.record(kind, frame=frame, **data)

    # ------------------------------------------------------------------
    # exporters
    # ------------------------------------------------------------------

    def snapshot(self, tracer=None, recorder_tail: Optional[int] = None) -> dict:
        """One structured, JSON-serializable report: metrics + flight
        recorder + a per-span summary of the tracer's ggrs_span_ms
        (GLOBAL_TRACER by default)."""
        if tracer is None:
            from ..utils.tracing import GLOBAL_TRACER as tracer
        return {
            "enabled": self.enabled,
            "taken_at_ms": time.time() * 1000.0,
            "metrics": self.registry.snapshot(),
            "events": self.recorder.to_json(recorder_tail),
            "tracer": {
                path: {
                    "count": s.count,
                    "mean_ms": s.sum / s.count,
                    "total_ms": s.sum,
                }
                for path, s in sorted(tracer.stats.items())
            },
        }

    def to_json(self, tracer=None, indent: Optional[int] = None) -> str:
        return json.dumps(self.snapshot(tracer), indent=indent)

    def prometheus(self) -> str:
        """Prometheus text exposition format (0.0.4)."""
        return "\n".join(self.registry.prometheus_lines()) + "\n"

    # ------------------------------------------------------------------
    # desync forensics
    # ------------------------------------------------------------------

    def desync_forensics(
        self,
        *,
        frame: int,
        local_checksum: int,
        remote_checksum: int,
        addr: Any,
        pending_predicted_inputs: Optional[List[dict]] = None,
        session: Optional[dict] = None,
        last_events: int = 64,
    ) -> dict:
        """Build (don't write) the forensics bundle for one desync."""
        return {
            "kind": "desync_forensics",
            "written_at_ms": time.time() * 1000.0,
            "frame": frame,
            "local_checksum": local_checksum,
            "remote_checksum": remote_checksum,
            "peer": jsonable(addr),
            "pending_predicted_inputs": pending_predicted_inputs or [],
            "events": self.recorder.to_json(last_events),
            "session": session or {},
        }

    def write_desync_forensics(self, **kwargs) -> Optional[str]:
        """Write the bundle to `<dump_dir>/ggrs_desync_f<frame>_<ts>.json`
        and return the path (None when the per-process dump cap is hit)."""
        if self._dumps_written >= self.MAX_FORENSICS_DUMPS:
            return None
        return self._write_bundle("desync", self.desync_forensics(**kwargs))

    def write_forensics(self, kind: str, *, frame: int = -1,
                        last_events: int = 64, **fields: Any) -> Optional[str]:
        """Generic forensics bundle — the desync writer's machinery for
        any device-domain verdict (slot quarantines, invariant trips):
        the caller's fields plus the flight-recorder tail land in one
        JSON dump under the same dir/cap discipline. Returns the path
        (None when the per-process dump cap is hit)."""
        if self._dumps_written >= self.MAX_FORENSICS_DUMPS:
            return None
        bundle = {
            "kind": f"{kind}_forensics",
            "written_at_ms": time.time() * 1000.0,
            "frame": frame,
            **{k: jsonable(v) for k, v in fields.items()},
            "events": self.recorder.to_json(last_events),
        }
        return self._write_bundle(kind, bundle)

    def _write_bundle(self, kind: str, bundle: dict) -> str:
        dump_dir = self.dump_dir or os.environ.get("GGRS_OBS_DUMP_DIR") or "."
        os.makedirs(dump_dir, exist_ok=True)
        path = os.path.join(
            dump_dir,
            f"ggrs_{kind}_f{bundle['frame']}_{int(bundle['written_at_ms'])}"
            f"_{self._dumps_written}.json",
        )
        with open(path, "w") as f:
            json.dump(bundle, f, indent=1)
        self._dumps_written += 1
        return path

    # ------------------------------------------------------------------

    def reset(self) -> None:
        """Zero metrics IN PLACE (pre-bound children stay valid), clear the
        event ring, re-arm the forensics dump cap."""
        self.registry.reset()
        self.recorder.clear()
        self._dumps_written = 0


# process-wide default, disabled unless opted in (mirrors GLOBAL_TRACER)
GLOBAL_TELEMETRY = Telemetry(enabled=False)


def enable_global_telemetry(dump_dir: Optional[str] = None) -> Telemetry:
    GLOBAL_TELEMETRY.enabled = True
    if dump_dir is not None:
        GLOBAL_TELEMETRY.dump_dir = dump_dir
    return GLOBAL_TELEMETRY
