"""Maps Phoenix node counts onto concrete JAX devices, for N tenants.

The provision service reasons in fungible node counts; this pool assigns
actual devices to named tenant groups: batch tenants (elastic trainers)
receive rectangular groups (multiples of the training job's model-parallel
width) so TP collectives stay intact; latency tenants (serving pools)
receive single devices per replica. A pool starts with no groups;
``add_group`` makes one per registered department.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax


class DevicePool:
    def __init__(self, devices: Optional[Sequence] = None):
        self.devices = list(devices if devices is not None else jax.devices())
        self.free = list(self.devices)
        self.groups: Dict[str, List] = {}

    @property
    def total(self) -> int:
        return len(self.devices)

    def add_group(self, name: str) -> None:
        assert name not in self.groups, name
        self.groups[name] = []

    def check(self):
        assigned = sum(len(g) for g in self.groups.values())
        assert len(self.free) + assigned == self.total, \
            (len(self.free), {k: len(v) for k, v in self.groups.items()},
             self.total)

    # -------------------------------------------------------- named groups
    def grant(self, name: str, n: int) -> List:
        """Move up to n free devices into the named group."""
        n = min(n, len(self.free))
        got, self.free = self.free[:n], self.free[n:]
        self.groups[name].extend(got)
        self.check()
        return got

    def reclaim(self, name: str, n: int) -> List:
        """Take n devices back from the named group (most recent first;
        the caller must resize/stop the workload on them)."""
        grp = self.groups[name]
        n = min(n, len(grp))
        got = grp[-n:] if n else []
        self.groups[name] = grp[:-n] if n else grp
        self.free.extend(got)
        self.check()
        return got

