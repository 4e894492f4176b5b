"""A run with its timed path broken underneath reads ``correct`` false,
for each fault a cell can have: a fold that returns its state unchanged,
half of a batch left out, the exchange between shards left out, and an
answer altered where it is produced."""

import numpy as np
import pytest
from conftest import ALL_CELLS, PEAKS, tiny

from bench.run import measure, result_line
from repro.core.pe.exec import ExecLayer
from repro.core.pe.pe import PE


def unchanged_state(mp):
    mp.setattr(PE, "write_region", lambda self, name, value: None)


def half_batch(mp):
    inner = ExecLayer.invoke_batch
    mp.setattr(ExecLayer, "invoke_batch",
               lambda self, exe, pays: inner(self, exe, pays[: max(1, len(pays) // 2)]))


def no_exchange(mp):
    mp.setattr(PE, "forward_ifunc", lambda self, dst, exe, pay: None)


def altered_answer(mp):
    inner = PE.return_payload

    def altered(self, dst, target, pay):
        pay = np.array(pay, copy=True)
        pay[-1] ^= 1  # the last data word: a row's last element, or the chase's result
        if target == "gather_return":
            pay[pay.size // 4:] ^= 1  # and most of the row words besides
        return inner(self, dst, target, pay)

    mp.setattr(PE, "return_payload", altered)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_answer": altered_answer}
# a cell whose batches hold one payload cannot leave half of one out
CASES = [(c, f) for c in ALL_CELLS for f in FAULTS
         if not (c == "chase-d64-c1" and f == "half_batch")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_reads_incorrect(name, fault, compiles, cpu):
    with pytest.MonkeyPatch.context() as mp:
        m = measure(tiny(name), 11, 0.3, cpu, PEAKS, compiles,
                    before_window=lambda cell: FAULTS[fault](mp))
    result = result_line(m, cpu, traced=False)
    assert not result["correct"] and result["failed"] > 0, result["checks"]
