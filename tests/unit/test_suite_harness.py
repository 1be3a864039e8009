"""The test harness's own rules: which modules compile at which backend level
(``tests/conftest.py``), and the replay of a tier-1 run's schedule
(``tools/suite_times.py``)."""

import importlib.util
import os

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_an_ordinary_module_compiles_cheaply_and_the_table_names_real_files(request):
    conftest = request.config.pluginmanager.getplugin(os.path.join(REPO, "tests", "conftest.py"))
    assert conftest.CHEAP_COMPILES is True
    assert jax.config.read("jax_disable_most_optimizations") is True
    table = conftest.DEFAULT_LEVEL
    assert {"tests/unit/ops/test_tpu_compile.py", "tests/unit/ops/test_tpu_compile_plan.py"} <= set(table)
    collected = [item.nodeid for item in request.session.items]
    for prefix, reason in table.items():
        path = prefix.split("::")[0]
        assert os.path.isfile(os.path.join(REPO, path)), path
        assert reason.strip(), prefix
        if any(nodeid.startswith(path) for nodeid in collected):     # a renamed case would drop out in silence
            assert any(nodeid.startswith(prefix) for nodeid in collected), prefix


@pytest.fixture(scope="module")
def suite_times():
    spec = importlib.util.spec_from_file_location("suite_times", os.path.join(REPO, "tools", "suite_times.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _junit(tmp_path, files):
    cases = "".join(
        f'<testcase classname="{name}" name="t{i}" time="{seconds / n}" />'
        for name, (seconds, n) in files.items() for i in range(n))
    path = tmp_path / "junit.xml"
    path.write_text(f'<testsuites><testsuite name="pytest">{cases}</testsuite></testsuites>')
    return str(path)


def test_replay_starts_the_file_of_many_cases_first_and_ends_on_the_long_one(suite_times, tmp_path, capsys):
    # seven files on six workers: the long file has the fewest cases, so it is
    # queued last, starts when the first worker falls free and ends the run
    files = {f"tests.unit.ops.test_f{i}": (60.0 + i, 10 + i) for i in range(5)}
    files["tests.unit.ops.test_many.TestClass"] = (30.0, 40)
    files["tests.benchmark.test_long"] = (300.0, 2)
    path = _junit(tmp_path, files)

    read = suite_times.by_file(path)
    assert read["tests/unit/ops/test_many.py"] == [pytest.approx(30.0), 40]   # the class is no file
    wall, last, spans = suite_times.replay(read)
    assert spans["tests/unit/ops/test_many.py"][0] == 0.0
    assert last == "tests/benchmark/test_long.py"
    assert spans[last] == (pytest.approx(30.0), pytest.approx(330.0))        # after test_many's worker
    assert wall == pytest.approx(suite_times.START_UP + 330.0)
    assert 330.0 > sum(s for s, _ in read.values()) / suite_times.WORKERS    # the sum alone misses it

    suite_times.main(["suite_times.py", path])
    out = capsys.readouterr().out
    assert "tests/unit/ops" in out and "projected wall 380 s (26 % of the 1470 s limit)" in out
    assert "ends last: tests/benchmark/test_long.py" in out
