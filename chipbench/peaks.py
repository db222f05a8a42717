"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports."""
from __future__ import annotations

from harness import HERE, load_json


def lookup(device_kind: str) -> dict:
    """The kind's peaks; a kind not in ``peaks.json`` is an error."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to peaks.json")
    return table[device_kind]
