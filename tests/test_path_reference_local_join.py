"""The one-chip join cell's plans (q3, q5, q18 under both streams of the
``join`` mix) with the Aggregate executor ("xla" segment ops, "kernel"
fused sweeps) and the join strategy ("sorted" searchsorted gather, "kernel"
join_probe) forced, each served on one CPU device and held to the plain
reference within ``tpch_sf1.join``'s limits (``_path_reference``)."""
import pytest

import _path_reference as M

NAME = "local_join"


@pytest.mark.parametrize("case", M.GROUPS[NAME].cases, ids=M.case_id)
def test_forced_path_meets_the_cell_limits(case):
    M.check_case(NAME, case)


def test_no_two_cases_lower_to_one_plan():
    M.check_distinct(NAME)
