"""Placement: bytes the program's transfer ledger booked in the window
as ``d2d.input`` (an input already on a device put onto the program's
own devices or mesh: a reshard, chip to chip) per frame counted.  0.0 in
a cell whose inputs are already where the program runs.  (Beside
``ledger_bytes_per_frame``, which leaves a metric out where the ledger
has no such row, because this one has no ``workloads`` list:
``tests/benchmark/toyroot.py`` maps every listed cell to a toy cell and
a four-chip cell has none.)"""

KEY = "d2d.input.bytes"


def read(obs: dict):
    if not obs.get("frames"):
        return None
    return obs["window"]["ledger"].get(KEY, 0) / obs["frames"]
