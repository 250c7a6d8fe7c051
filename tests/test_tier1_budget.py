"""What keeps tier-1 inside the driver's clock (ROADMAP, "Tier-1's
budget"): every test has a limit of its own, and a whole program
compiled for the described chip is ``slow``. Pure Python: the source is
read, nothing of it is collected or imported here."""

import ast
import os
import subprocess
import sys
import textwrap
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
CHIP_COMPILE = os.path.join(TESTS, "ops", "test_chip_compile.py")


def _files_that_say(text):
    """The files under ``tests/`` (this one apart) that hold ``text``."""
    found = []
    for folder, _, names in os.walk(TESTS):
        for name in names:
            path = os.path.join(folder, name)
            if (name.endswith(".py") and path != os.path.abspath(__file__)
                    and text in open(path).read()):
                found.append(os.path.relpath(path, TESTS))
    return found


def _is_slow(mark):
    return ast.unparse(mark) == "pytest.mark.slow"


def test_one_file_describes_the_chip():
    """``topologies.get_topology_desc`` loads the TPU's library, which
    one process at a time may hold: all such compiles are one file's,
    so under ``--dist loadfile`` one worker's."""
    assert _files_that_say("get_topology_desc") == [
        os.path.relpath(CHIP_COMPILE, TESTS)]


def test_one_way_into_the_tpu_interpreter():
    """``pltpu.force_tpu_interpret_mode()`` is entered by
    ``conftest.py``'s ``interpreted_kernels`` alone, which holds the
    process to what makes the interpreter's callbacks safe."""
    assert _files_that_say("force_tpu_interpret_mode(") == ["conftest.py"]


def test_whole_programs_for_the_chip_are_slow():
    """A whole microbatch or a whole train step compiled by the chip's
    compiler on the host takes one to three minutes: every test of
    that file named ``whole_`` is marked ``slow``, all its cases."""
    tree = ast.parse(open(CHIP_COMPILE).read())
    whole = [node for node in tree.body
             if isinstance(node, ast.FunctionDef)
             and node.name.startswith("test_") and "whole_" in node.name]
    assert len(whole) >= 4
    unmarked = [node.name for node in whole
                if not any(_is_slow(mark) for mark in node.decorator_list)]
    assert unmarked == []


def test_a_test_past_its_limit_ends_its_process_and_is_named(tmp_path):
    """``conftest.py:TEST_LIMIT_S``: a test that sits (here a sleep,
    in earnest a deadlock in native code) ends its process with every
    thread's stack on stderr, so under xdist that test is reported
    failed by name and the file's other tests go to a new worker."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "tier1_conftest", {os.path.join(TESTS, "conftest.py")!r})
        tier1 = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tier1)
        tier1.TEST_LIMIT_S = 2
        pytest_runtest_protocol = tier1.pytest_runtest_protocol
        """))
    (tmp_path / "test_sits.py").write_text(textwrap.dedent("""
        import time

        def test_sits_past_its_limit():
            open("began", "w").write(repr(time.time()))
            time.sleep(60)

        def test_never_reached():
            pass
        """))
    child = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "--rootdir", str(tmp_path),
         str(tmp_path / "test_sits.py")],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=os.path.dirname(TESTS)))
    # (from the test's own start: the child's imports are not the limit's)
    assert time.time() - float((tmp_path / "began").read_text()) < 15
    assert child.returncode != 0
    assert "Timeout (0:00:02)!" in child.stderr
    assert "in test_sits_past_its_limit" in child.stderr
    assert "passed" not in child.stdout
