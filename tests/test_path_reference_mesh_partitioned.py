"""The four-chip join cell's plans (q3, q5, q18 under both streams of the
``join`` mix) under FIRST_TOUCH, LOCAL_ALLOC and PREFERRED, with every
distributed Join forced to route both sides to the key's owner shards and
the distributed TopK forced to "replicated", served on four CPU devices
and held to the plain reference within ``tpch_sf1_x4.join``'s limits
(``_path_reference``); replicated and candidates TopK give the same
bits."""
import pytest

import _path_reference as M

NAME = "mesh_partitioned"


@pytest.mark.parametrize("case", M.GROUPS[NAME].cases, ids=M.case_id)
def test_forced_path_meets_the_cell_limits(case):
    M.check_case(NAME, case)


def test_no_two_cases_lower_to_one_plan():
    M.check_distinct(NAME)


@pytest.mark.parametrize("base", M.topk_bases(NAME))
def test_topk_lowerings_give_the_same_bits(base):
    M.check_topk_bits(NAME, base)
