"""Seconds a step that its MFCs spend on the host in interface code:
packing, ``device_get``, regrouping, the runner's hooks. The sum over
the step's ``mfc:*`` spans of each one's time outside every engine
program (``engine:*``, ended blocked) and every reshard (``realloc``)
beneath it. Thread seconds, not wall: ``ref_inf`` and ``rew_inf`` run
in two threads. From the program's own capture of the steps in which
every span was synced (after the profiled ones; waiting after each
program is what tells host from device, and what a profiled step must
not do: it exposes host work the interface hides); median over them;
nothing from a capture whose engine programs did not end blocked."""

from benchmark import program_capture


def read(record):
    capture = program_capture.last(program_capture.all_synced)
    if capture is None:
        return None

    def below(span):
        return span["name"].startswith("engine:") \
            or span["name"] == "realloc"

    def host_seconds(spans):
        mfcs = [s for s in spans if s["name"].startswith("mfc:")]
        if not mfcs:
            return None
        return sum(capture.self_seconds(m, cover=below) for m in mfcs)

    return program_capture.median_over_steps(capture, host_seconds)
