"""The CLI (`train_agent_apex.py --architecture r2d2 --core-config <file>
--role apex`) with every non-LSTM core, at tiny widths: the apex R2D2 driver
over four learner devices.  The run is tests/core_families.py's; a file a role
(tests/test_core_cli_{anakin,single,apex,fused}.py): these are the slowest
cases of the cores' tests, and the suite runs a file a worker.  With six
families this role's cases sum to 370 s among six workers, so the table's
cores stand in two files, the first three by name in
tests/test_core_cli_apex.py, the rest here (no file may hold more than
400 s of test time)."""

import pytest

import core_families as cf


@pytest.mark.parametrize("core", sorted(cf.CORES)[3:])
@pytest.mark.parametrize("role,learners", [("apex", 4)])
def test_host_fed_roles_train_with_the_core(tmp_path, role, learners, core):
    cf.host_fed_role_trains_with_the_core(tmp_path, role, learners, core)
