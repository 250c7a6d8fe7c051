"""ModelHost.execute_level contract: outputs in input order, per-node
exec_infos populated, concurrent threads actually used for >1 node
where the host has more than one CPU, and in order where it has one."""

import threading
import time


class _FakeHost:
    """Only the pieces execute_level touches."""
    from realhf_tpu.system.model_host import ModelHost
    execute_level = ModelHost.execute_level

    def __init__(self, sleep_s=0.0):
        self.exec_infos = {}
        self._sleep = sleep_s
        self.threads_seen = set()
        self._lock = threading.Lock()

    def execute(self, node_name, inp):
        with self._lock:
            self.threads_seen.add(threading.get_ident())
        time.sleep(self._sleep)
        self.exec_infos[node_name] = dict(node=node_name, secs=self._sleep)
        return f"out:{node_name}:{inp}"


class TestExecuteLevel:

    def test_outputs_in_input_order(self):
        host = _FakeHost()
        named = [(f"n{i}", i) for i in range(5)]
        outs = host.execute_level(named)
        assert outs == [f"out:n{i}:{i}" for i in range(5)]
        assert set(host.exec_infos) == {f"n{i}" for i in range(5)}

    def test_concurrent_threads_for_multi_node_level(self, monkeypatch):
        # deterministic overlap proof: every execute() waits at a
        # shared barrier, which only releases when all three calls are
        # in flight SIMULTANEOUSLY -- no wall-clock bound to flake on
        # a loaded box. The host is given two CPUs here (the mechanism
        # is what's under test, not the gate).
        import realhf_tpu.system.model_host as mh
        monkeypatch.setattr(mh.os, "cpu_count", lambda: 2)
        host = _FakeHost()
        barrier = threading.Barrier(3)
        orig = host.execute

        def execute(node_name, inp):
            barrier.wait(timeout=30)
            return orig(node_name, inp)

        host.execute = execute
        outs = host.execute_level([("a", 1), ("b", 2), ("c", 3)])
        assert outs == ["out:a:1", "out:b:2", "out:c:3"]
        assert len(host.threads_seen) == 3

    def test_single_cpu_defaults_to_serial(self, monkeypatch):
        # concurrent XLA CPU collectives spin-wait their rendezvous;
        # one core starves them into deadlock -- the level must run in
        # order there
        import realhf_tpu.system.model_host as mh
        monkeypatch.setattr(mh.os, "cpu_count", lambda: 1)
        host = _FakeHost()
        outs = host.execute_level([("a", 1), ("b", 2)])
        assert outs == ["out:a:1", "out:b:2"]
        assert host.threads_seen == {threading.get_ident()}

    def test_single_node_stays_on_caller_thread(self):
        host = _FakeHost()
        host.execute_level([("only", 0)])
        assert host.threads_seen == {threading.get_ident()}
