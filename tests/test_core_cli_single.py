"""The CLI (`train_agent_apex.py --architecture r2d2 --core-config <file>
--role single`) with every non-LSTM core, at tiny widths: `train_r2d2`.  The
run is tests/core_families.py's; a file a role
(tests/test_core_cli_{anakin,single,apex,fused}.py): these are the slowest
cases of the cores' tests, and the suite runs a file a worker."""

import pytest

import core_families as cf


@pytest.mark.parametrize("core", sorted(cf.CORES))
@pytest.mark.parametrize("role,learners", [("single", 1)])
def test_host_fed_roles_train_with_the_core(tmp_path, role, learners, core):
    cf.host_fed_role_trains_with_the_core(tmp_path, role, learners, core)
