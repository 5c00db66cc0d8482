"""Published peaks of each accelerator, keyed by JAX's ``device_kind``
(bench/peaks.json, with its source). A device not in the table is an
error: no default."""
from __future__ import annotations

from harness.spec import BENCH, load_json


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json ({', '.join(table['devices'])})")
    return dict(table["devices"][device_kind], source=table["source"])
