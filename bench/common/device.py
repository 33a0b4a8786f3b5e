"""The chips a run uses, as JAX reports them, and what JAX compiles."""
from __future__ import annotations


def peak_bytes(devices) -> list[int]:
    """``peak_bytes_in_use`` of each device (0 where not reported)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class CompileCounter:
    """Records each program JAX compiles or loads from its persistent
    cache (one backend-compile event either way), by name, so a run can
    report which of them fell inside its window."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.names: list[str] = []
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, fun_name: str = "?",
                     **_) -> None:
        if event == self._BACKEND:
            self.names.append(fun_name)

    @property
    def total(self) -> int:
        return len(self.names)
