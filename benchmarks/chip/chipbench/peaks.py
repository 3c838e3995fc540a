"""Published peaks per device kind, from ``peaks.json``. A device that is not
in the table is an error, not a default."""

from __future__ import annotations

from chipbench.spec import ROOT, load_json


def peaks(device_kind: str) -> dict:
    table = load_json(ROOT / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
