"""What the cells the window drives share; each kind of service has its
own module under ``kinds/`` (see ``kinds/__init__.py``).

Each cell builds its data from the seed, builds the system under test
through its public entry points, and then exposes one ``step()``: one
scheduler round that retires what completed and, in a closed loop,
submits a new request for each one retired.  Every retirement is
recorded with the harness's own host clock (submit -> the host seeing the
result) and with what the system answered, for the check after the
window.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from . import spec


@dataclass
class Retired:
    """One retired request: which request (its index in the traffic's
    pool), what the system answered, and when (host clock)."""

    index: int
    answer: object
    t_submit: float
    t_done: float


@dataclass
class Counters:
    ticks: int = 0  # scheduler rounds the harness drove
    invokes: int = 0  # XLA dispatches over all PEs (PEStats)
    invoked_payloads: int = 0  # payloads those dispatches retired (PEStats)
    puts: int = 0  # frames on the fabric (TrafficStats)
    jit_ms: float = 0.0  # PEStats.jit_ms_total over all PEs

    def minus(self, other: "Counters") -> "Counters":
        return Counters(**{k: getattr(self, k) - getattr(other, k) for k in self.__dict__})


@dataclass
class ClosedLoop:
    """A closed loop over a ``repro.core.Cluster`` (``self.cluster``): the
    records, the counters, the fill and the drain."""

    concurrency: int
    spans: object
    done: list = field(default_factory=list)  # Retired, in retirement order
    ticks: int = 0
    next_index: int = 0

    def counters(self) -> Counters:
        pes = self.cluster.pes()
        return Counters(
            ticks=self.ticks,
            invokes=sum(pe.stats.invokes for pe in pes),
            invoked_payloads=sum(pe.stats.invoked_payloads for pe in pes),
            puts=self.cluster.fabric.stats.puts,
            jit_ms=sum(pe.stats.jit_ms_total for pe in pes),
        )

    def fill(self) -> None:
        """Put the closed loop's ``concurrency`` requests in flight."""
        while self.in_flight() < self.concurrency:
            self.submit()

    def drain(self, limit_s: float = 60.0, idle_rounds: int = 50) -> None:
        """Stop submitting and run until every request in flight has
        retired, the system makes no progress for ``idle_rounds`` rounds,
        or ``limit_s`` seconds pass."""
        end, idle = time.perf_counter() + limit_s, 0
        while self.in_flight() and idle <= idle_rounds and time.perf_counter() < end:
            idle = 0 if self.step(resubmit=False) else idle + 1


def refuse_unless(traffic: dict, kind: str, arrivals: tuple) -> None:
    """Raise unless ``traffic`` is of the ``kind`` and one of the
    ``arrivals`` that the kind module calling this drives."""
    if traffic["kind"] != kind or traffic["arrival"] not in arrivals:
        raise ValueError(f"traffic {traffic} does not fit a kind that drives {kind!r} "
                         f"traffic with arrivals {list(arrivals)}")


def placed_on(cluster, devices) -> None:
    """Raise unless every PE of ``cluster`` computes on one of the cell's
    ``devices``."""
    off = [pe.name for pe in cluster.pes() if pe.device not in devices]
    if off:
        raise ValueError(f"PEs {off} are not on the cell's devices {list(devices)}")


def build_cell(config: dict, traffic: dict, seed: int, spans, devices):
    """The ``Cell`` of the kind module ``config["service"]`` names (see
    ``kinds/__init__.py``), under ``traffic``, on ``devices``."""
    return spec.load_kind(config["service"]).Cell(config, traffic, seed, spans, devices)
