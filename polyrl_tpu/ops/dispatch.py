"""Which implementation each kernel dispatcher took.

Every dispatcher in ``ops/`` (``paged_attention``, ``paged_kv_write``,
``grouped_paged_attention``, ``flash_attention_train``) chooses between a
TPU kernel and a jnp path from the platform and the shapes. The choice is
made while tracing, so it is invisible in a compiled step; each dispatcher
notes it here and ``chip_smoke.py`` prints the table and fails a chip run
that took an oracle path.
"""

from __future__ import annotations

# kernel name -> implementations taken since the last reset()
_TAKEN: dict[str, set[str]] = {}


def note(kernel: str, impl: str) -> None:
    _TAKEN.setdefault(kernel, set()).add(impl)


def taken() -> dict[str, tuple[str, ...]]:
    """Snapshot: kernel -> sorted implementations taken."""
    return {k: tuple(sorted(v)) for k, v in _TAKEN.items()}


def reset() -> None:
    _TAKEN.clear()
