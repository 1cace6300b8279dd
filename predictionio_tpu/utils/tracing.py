"""Tracing/profiling — a first-class improvement over the reference.

The reference has no tracing at all (SURVEY §5: observability = logs +
the external Spark UI). Here the XLA profiler is wired into the
workflow: ``trace(dir)`` captures a device trace viewable in
TensorBoard/XProf/Perfetto, and ``annotate(name)`` labels host-side
phases so they show up on the host plane of that same trace, on the
profiler's clock, beside the device's operations.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Any, ContextManager, Iterator, Optional

log = logging.getLogger(__name__)


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture an XLA device trace under ``log_dir`` (no-op when None).

    View with TensorBoard's profile plugin or ui.perfetto.dev.
    """
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("XLA trace written to %s", log_dir)


class _NoAnnotation:
    """Stands in where jax is absent: profiling must never break the
    workflow."""

    def __init__(self, name: str, **stats: Any) -> None:
        pass

    def __enter__(self) -> "_NoAnnotation":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass

    def set_metadata(self, **stats: Any) -> None:
        pass


_annotation = None  # jax.profiler.TraceAnnotation, resolved on first use


def annotate(name: str, **stats: Any) -> ContextManager:
    """Label a host-side phase on the profiler timeline; ``stats``
    become the event's stats (``set_metadata(**more)`` on the entered
    object adds what is known only later). With no capture running the
    annotation is inert (under a microsecond)."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation as _annotation
        except ImportError:
            _annotation = _NoAnnotation
    return _annotation(name, **stats)
