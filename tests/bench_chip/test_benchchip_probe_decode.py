"""A traced run wraps the table plan's decode in the benchmark's probe
(``bench.core.decode`` spans and the kernel's work counts).  The program
has to run, correct, under that wrapper in every cell: the kernel path's
arena mirror travels inside the codes argument, not as an argument the
wrapper does not pass on."""

import pytest

from benchchip_util import tiny_run
from benchmarks.chip.probe import Probe


@pytest.mark.parametrize("workload", ["tpcc_w1.mix", "tpcc_w1.readonly",
                                      "chbench_w1.q1q6"])
def test_a_run_under_the_probe_is_correct_and_counts_kernel_calls(workload):
    probe = Probe().install()
    probe.counting = True
    try:
        res = tiny_run(workload)
    finally:
        probe.counting = False
        probe.uninstall()
    assert res["correct"], res["checks"]
    assert probe.work["calls"] >= 1
    assert probe.work["rows"] >= probe.work["calls"]
