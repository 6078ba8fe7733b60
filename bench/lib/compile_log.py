"""Count the programs JAX compiles, so a run can say how many compiled
inside its measured window (there should be none)."""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Backend compile durations, by function name, as JAX reports them.
    A program loaded from the persistent compilation cache appears too,
    with the (short) time its lookup and load took."""

    def __init__(self):
        import jax.monitoring
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((kw.get("fun_name", "?"), duration))

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int):
        return self.events[mark:]

    def report(self, mark: int = 0) -> str:
        ev = self.since(mark)
        big = [(n, round(s, 2)) for n, s in ev if s >= 1.0]
        return (f"{len(ev)} compiles, {sum(s for _, s in ev):.2f} s; "
                f"of 1 s or more: {big}")
