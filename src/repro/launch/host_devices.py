"""``--devices N`` preamble shared by the CLI launchers.

The flag only forks virtual *CPU* devices (XLA's host platform device
count): on a TPU it changes nothing, and the mesh is the chips present.
XLA locks the host device count at first backend initialization, so the
flag must be applied to ``XLA_FLAGS`` *before anything imports jax* — the
launchers call :func:`preparse_devices` at module import, ahead of their
jax imports, and this module must therefore never import jax itself.
"""
from __future__ import annotations

import os
import sys
from typing import Optional, Sequence

#: ``--help`` text of the launchers' ``--devices`` flag.
DEVICES_HELP = (
    "fork N virtual CPU devices before jax starts (CPU only: the flag "
    "changes nothing on a TPU, where the chips present are the devices)"
)


def preparse_devices(argv: Optional[Sequence[str]] = None) -> Optional[int]:
    """Scan argv for ``--devices N`` / ``--devices=N`` and set XLA_FLAGS.

    Appends to any pre-existing ``XLA_FLAGS`` rather than clobbering it
    (unless a host-device-count flag is already present, which wins).
    Returns the parsed count, or None if the flag is absent.
    """
    argv = list(sys.argv if argv is None else argv)
    n: Optional[str] = None
    for i, arg in enumerate(argv):
        if arg == "--devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif arg.startswith("--devices="):
            n = arg.split("=", 1)[1]
    if n is None or int(n) <= 0:
        return None
    prev = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev:
        flag = f"--xla_force_host_platform_device_count={int(n)}"
        os.environ["XLA_FLAGS"] = f"{prev} {flag}".strip()
    return int(n)
