"""Compile probes for the Pallas kernels: does the attached TPU's
compiler accept a kernel at the shapes about to run, and if not, what
did it say. The compiler's message is the result, never swallowed."""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax

#: what a probe answers where no TPU is attached (interpret mode is for
#: CPU tests only, so there is nothing to compile)
NO_TPU = "no TPU backend attached"


def tpu_attached() -> bool:
    return jax.default_backend() == "tpu"


class CompileProbes:
    """Outcomes of one kernel's compile probes in this process, keyed by
    the shape parameters probed (a tuple of short strings such as
    ``("r64", "float32", "L512")``): None = compiled, str = the
    compiler's message."""

    def __init__(self) -> None:
        self._seen: Dict[Tuple[str, ...], Optional[str]] = {}

    def refusal(self, key: Tuple[str, ...],
                compile_fn: Callable[[], object]) -> Optional[str]:
        """None when ``compile_fn`` compiles on the attached TPU, else
        the compiler's message (probed once per ``key``)."""
        if not tpu_attached():
            return NO_TPU
        if key not in self._seen:
            try:
                compile_fn()
                self._seen[key] = None
            except Exception as e:  # noqa: BLE001 — the message IS the result
                self._seen[key] = f"{type(e).__name__}: {e}"
        return self._seen[key]

    def refusals(self) -> Dict[str, str]:
        """Every refusal so far, ``"/".join(key)`` → compiler message —
        what the train log line and ``/status.json`` carry for kernels
        ``auto`` skipped."""
        return {"/".join(k): msg for k, msg in sorted(self._seen.items())
                if msg is not None}

    def clear(self) -> None:
        self._seen.clear()
