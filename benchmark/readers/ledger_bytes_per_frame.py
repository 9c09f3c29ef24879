"""Bytes the program's transfer ledger (``obs/transfer.py``) booked in
the window under one ``<direction>.<reason>`` key, per frame counted."""


def read(obs: dict, key: str):
    ledger = obs["window"]["ledger"]
    if key not in ledger or not obs["frames"]:
        return None
    return ledger[key] / obs["frames"]
