"""The scan cell's plans (q1, q6 under the three streams of the ``scan``
mix) on the four-chip configuration's mesh under each placement policy,
served on four CPU devices and held to the plain reference within
``tpch_sf10.scan``'s limits (``_path_reference``)."""
import pytest

import _path_reference as M

NAME = "mesh_scan"


@pytest.mark.parametrize("case", M.GROUPS[NAME].cases, ids=M.case_id)
def test_forced_path_meets_the_cell_limits(case):
    M.check_case(NAME, case)


def test_no_two_cases_lower_to_one_plan():
    M.check_distinct(NAME)
