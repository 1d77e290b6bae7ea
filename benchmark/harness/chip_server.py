"""The chip-owning child of every benchmark run, traced or not.

Starts one small control thread, then runs `python -m seaweedfs_tpu server ...`
in the main thread exactly as the module entry point does. Only the process
that holds the chip can trace it or read its memory, so the parent asks by
dropping files into the control directory and this thread answers with files:

  trace.start  (text: a directory) -> jax.profiler.start_trace(dir); -> trace.started
  trace.stop                      -> jax.profiler.stop_trace();      -> trace.stopped
  stats                           -> device memory_stats as JSON     -> stats.json

With `--trace 0` the parent never writes `trace.*`, so the profiler is never
started. Nothing here touches jax before it is asked to.

  python benchmark/harness/chip_server.py <control_dir> <server arguments...>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _answer(control: str, name: str, text: str) -> None:
    tmp = os.path.join(control, name + ".tmp")
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, os.path.join(control, name))


def _take(control: str, name: str) -> str | None:
    path = os.path.join(control, name)
    try:
        with open(path) as f:
            text = f.read()
    except FileNotFoundError:
        return None
    os.remove(path)
    return text


def control_loop(control: str) -> None:
    annotation = None
    while True:
        try:
            target = _take(control, "trace.start")
            if target is not None:
                import jax

                # no Python call-stack tracer: it slows the host it watches and
                # fills the trace; host spans are the `tracing` issue's
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(target.strip(), profiler_options=options)
                annotation = jax.profiler.TraceAnnotation("bench.window")
                annotation.__enter__()
                _answer(control, "trace.started", repr(time.monotonic()))
            if _take(control, "trace.stop") is not None:
                import jax

                if annotation is not None:
                    annotation.__exit__(None, None, None)
                    annotation = None
                jax.profiler.stop_trace()
                _answer(control, "trace.stopped", repr(time.monotonic()))
            if _take(control, "stats") is not None:
                import jax

                per_dev = [d.memory_stats() or {} for d in jax.local_devices()]
                _answer(control, "stats.json", json.dumps({
                    "memory_peak_bytes": max(
                        (int(s.get("peak_bytes_in_use", 0)) for s in per_dev), default=0
                    ),
                    "devices": len(per_dev),
                }))
        except Exception as e:  # noqa: BLE001 — reported to the parent, never passed over
            _answer(control, "error", f"{type(e).__name__}: {e}")
        time.sleep(0.01)


def _break_apply_matrix() -> None:
    """Tests only (`run.py --fault broken_apply`): alter the answer where it is
    produced. Every GF(2^8) apply on the device — encode, rebuild, degraded
    read — gets the first byte of each output row flipped, so that the
    benchmark's check is seen to come out false with the timed path broken."""
    from seaweedfs_tpu.ops import rs_jax

    sound = rs_jax.apply_matrix

    def broken(m, shards, donate=False):
        out = sound(m, shards, donate=donate)
        return out.at[..., 0].set(out[..., 0] ^ 1)

    rs_jax.apply_matrix = broken


def main(argv: list[str]) -> int:
    control, server_args = argv[0], argv[1:]
    sys.path.insert(0, os.getcwd())  # the checkout's root: `seaweedfs_tpu`
    threading.Thread(target=control_loop, args=(control,), daemon=True).start()
    if os.environ.get("WEEDBENCH_BREAK") == "apply_matrix":
        _break_apply_matrix()
    from seaweedfs_tpu.__main__ import main as weed_main

    return weed_main(["server", *server_args])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
