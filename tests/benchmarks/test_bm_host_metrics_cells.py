"""A traced rehearsal of each cell that has a configuration to itself
holds the ten readings of the host's timeline (ISSUE 38); a file of its
own so that the six rehearsals spread over two of the tests' workers."""
import pytest

from test_bm_host_metrics import traced_rehearsal_holds_the_ten


@pytest.mark.parametrize("workload", [
    "gpt2_large.seq1k", "nemotron3_super_l11.seq8k",
    "granite4_h_micro_l10.seq8k"])
def test_a_traced_rehearsal_holds_the_ten(workload):
    traced_rehearsal_holds_the_ten(workload)
