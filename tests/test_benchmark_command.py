"""The reader of a command's own trace, where the driver's tier-1 command
collects it: the cases of `benchmark/tests/test_command_reduce.py` against
`benchmark/harness/command_reduce.py`, imported read-only from the benchmark's
own file (as `tests/test_benchmark_rate.py` does for the rates), and ONE case
the benchmark's directory cannot hold: a live profiler session on this process
(CPU), with the mirror the chip-owning server installs, a master that receives
a script's trace, and the reader run over what the session wrote."""

import glob
import importlib.util
import io
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _benchmarks_cases():
    """`benchmark/tests/test_command_reduce.py` under a name of its own: it
    imports `harness` from the benchmark's directory, which is on the path for
    as long as that takes."""
    sys.path.insert(0, BENCH_DIR)
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_tests_test_command_reduce", os.path.join(BENCH_DIR, "tests", "test_command_reduce.py")
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH_DIR)
    return module


_cases = _benchmarks_cases()
command_reduce, span_reduce = _cases.command_reduce, _cases.span_reduce
recorded = _cases.recorded
for _name in dir(_cases):
    if _name.startswith("test_"):
        globals()[_name] = getattr(_cases, _name)


def test_a_live_profiler_session_holds_the_whole_command_and_the_reader_reads_it(tmp_path, monkeypatch):
    """`shell.trace` and `unix_ns` under a real profiler session: the script's
    spans, made on this process's monotonic clock and sent as offsets from a
    wall-clock birth, land on the profiler's clock inside the `rpc.server`
    roots the master recorded for the same calls, to well under a millisecond."""
    import jax

    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.command import servers
    from seaweedfs_tpu.obs import trace
    from seaweedfs_tpu.shell import CommandEnv, run_script

    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    env = CommandEnv(master.address)
    jax_codec = types.SimpleNamespace(store=types.SimpleNamespace(encoder=types.SimpleNamespace(backend="jax")))
    try:
        servers._mirror_spans_to_profiler(jax_codec)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            now = time.monotonic()
            run_script(env, "lock; volume.list; unlock", io.StringIO(),
                       started=(now - 0.05, now - 0.04, now - 0.02, now - 0.001))
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.set_mirror(None)
        env.close()
        master.stop()
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    stretch = span_reduce.reduce_spans(str(tmp_path), set(trace.SPAN_NAMES))
    events = [e for t in stretch["threads"] for e in t]
    (receipt,) = [e for e in events if e[0] == "shell.trace"]
    assert set(receipt[3]) >= {"trace_id", "birth_unix_ns", "names", "what", "t_ns", "dur_ns", "depth", "thread"}
    roots = [e for e in events if e[0] == "rpc.server"]
    assert len(roots) == 3 and all(isinstance(e[3]["unix_ns"], int) and e[3]["trace_id"] == receipt[3]["trace_id"]
                                   for e in roots)
    assert receipt[1] >= max(e[1] + e[2] for e in roots)  # the receipt falls after the command's last RPC
    (script,) = command_reduce.commands(stretch)
    assert [s[1] for s in script["spans"] if s[0] == "shell.command"] == ["lock", "volume.list", "unlock"]
    assert script["start_ms"] == [pytest.approx(10.0, abs=0.01), pytest.approx(20.0, abs=0.01),
                                  pytest.approx(19.0, abs=0.01)]
    check = command_reduce.clock_check(stretch, script)
    assert check["roots"] == check["matched"] == 3 and check["largest_violation_us"] < 1000.0
    facts = {"traces": [{"chips": []}], "spans": [stretch]}
    assert command_reduce.command_span_ms(facts, "volume.list", "shell.start") == pytest.approx(
        script["spans"][1][3] * 1e3)
    assert command_reduce.head_attributed_pct(facts, "volume.list") is None  # a CPU session: no device plane
