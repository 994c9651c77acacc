"""Node fingerprinting — attribute/resource discovery.

Reference: ``client/fingerprint/`` (arch, cpu, memory, storage, network,
kernel — fingerprint.go:31-51). Host facts come from os/platform; TPU
presence is fingerprinted from the environment so the scheduler can target
accelerator nodes (the devices analog of the reference's nvidia plugin).
"""

from __future__ import annotations

import os
import platform
from typing import Dict, Tuple

from ..structs.types import NodeResources


def fingerprint() -> Tuple[Dict[str, str], NodeResources]:
    attrs: Dict[str, str] = {
        "kernel.name": platform.system().lower(),
        "kernel.version": platform.release(),
        "os.name": platform.system().lower(),
        "os.version": platform.version(),
        "cpu.arch": platform.machine(),
    }
    ncpu = os.cpu_count() or 1
    attrs["cpu.numcores"] = str(ncpu)

    mem_mb = 4096
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page_size = os.sysconf("SC_PAGE_SIZE")
        mem_mb = int(pages * page_size / (1024 * 1024))
    except (ValueError, OSError, AttributeError):
        pass
    attrs["memory.totalbytes"] = str(mem_mb * 1024 * 1024)

    disk_mb = 50 * 1024
    try:
        st = os.statvfs("/")
        disk_mb = int(st.f_bavail * st.f_frsize / (1024 * 1024))
    except OSError:
        pass

    # TPU fingerprint (the accelerator analog of devices/gpu/nvidia).
    devices: Dict[str, list] = {}
    tpu_gen = os.environ.get("TPU_ACCELERATOR_TYPE")
    if tpu_gen:
        attrs["platform.tpu.type"] = tpu_gen.split(":")[0].split("-")[0]
        devices["tpu"] = ["tpu0"]

    resources = NodeResources(
        cpu=ncpu * 1000,  # MHz shares approximation
        memory_mb=mem_mb,
        disk_mb=disk_mb,
        devices=devices,
    )
    return attrs, resources
