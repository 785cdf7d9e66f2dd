"""The program's byte ledger of both links (``bytes_up`` + ``bytes_down``)
added in the window, per committed update, in MB."""


def read(run):
    if not run.updates or not run.wire_bytes:
        return None
    return run.wire_bytes / run.updates / 1e6
