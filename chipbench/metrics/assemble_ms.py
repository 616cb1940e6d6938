"""Store to device: time in the program's span ``fimi/assemble_store`` per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("fimi/assemble_store")
