"""Native C++ TCPStore (core/native/tcp_store.cc via ctypes): in-process
KV/wait/add semantics + a REAL two-process rendezvous (the reference's
multi-process-single-host test pattern, SURVEY §4)."""
import os
import subprocess
import sys
import threading
import time

import pytest

from paddle_tpu.distributed import TCPStore


class TestInProcess:
    def test_set_get_add(self):
        m = TCPStore(is_master=True, world_size=1)
        w = TCPStore(port=m.port)
        try:
            m.set("k", b"v1")
            assert w.get("k") == b"v1"
            assert w.add("c", 3) == 3
            assert m.add("c", 2) == 5
            # counters are also visible as keys (8-byte little-endian)
            assert int.from_bytes(m.get("c"), "little") == 5
        finally:
            w.close()
            m.close()

    def test_get_blocks_until_set(self):
        m = TCPStore(is_master=True)
        w = TCPStore(port=m.port)
        try:
            got = {}

            def waiter():
                got["v"] = w.get("late", timeout=5.0)

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.2)
            m.set("late", b"now")
            t.join(timeout=5)
            assert got["v"] == b"now"
        finally:
            w.close()
            m.close()

    def test_timeout(self):
        m = TCPStore(is_master=True)
        try:
            with pytest.raises(TimeoutError):
                m.get("never", timeout=0.2)
        finally:
            m.close()

    def test_barrier_two_clients(self):
        m = TCPStore(is_master=True, world_size=2)
        w = TCPStore(port=m.port, world_size=2)
        try:
            done = []

            def other():
                w.barrier("b0", timeout=5.0)
                done.append("w")

            t = threading.Thread(target=other)
            t.start()
            m.barrier("b0", timeout=5.0)
            t.join(timeout=5)
            assert done == ["w"]
        finally:
            w.close()
            m.close()


_WORKER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu.distributed import TCPStore

port = int(sys.argv[1])
store = TCPStore(port=port, world_size=2, timeout=15.0)
store.set("worker/ready", b"1")
val = store.get("master/payload", timeout=10.0)
store.set("worker/echo", val + b"-seen")
store.barrier("fin", timeout=10.0)
store.close()
print("WORKER_OK")
"""


class TestTwoProcesses:
    def test_cross_process_rendezvous(self, tmp_path):
        master = TCPStore(is_master=True, world_size=2, timeout=15.0)
        script = tmp_path / "worker.py"
        script.write_text(_WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen([sys.executable, str(script),
                                 str(master.port)],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, env=env,
                                text=True)
        try:
            assert master.get("worker/ready", timeout=30.0) == b"1"
            master.set("master/payload", b"token42")
            assert master.get("worker/echo", timeout=10.0) == b"token42-seen"
            master.barrier("fin", timeout=10.0)
            out, _ = proc.communicate(timeout=30)
            assert proc.returncode == 0, out
            assert "WORKER_OK" in out
        finally:
            if proc.poll() is None:
                proc.kill()
            master.close()


class TestNativeHostTracer:
    """C++ host tracer (core/native/host_tracer.cc) behind
    paddle.profiler.RecordEvent."""

    def test_spans_recorded_natively_and_exported(self, tmp_path):
        import paddle_tpu.profiler as prof
        from paddle_tpu.profiler import native_tracer

        assert native_tracer.available()
        p = prof.Profiler(targets=[prof.ProfilerTarget.CPU],
                          scheduler=(0, 2))
        p.start()
        with prof.RecordEvent("native-span"):
            time.sleep(0.005)
        p.step()
        with prof.RecordEvent("native-span-2"):
            time.sleep(0.002)
        p.stop()
        # spans flowed through the native sink into the profiler result
        names = {e.name for e in p._all_events}
        assert "native-span" in names or "native-span-2" in names

    def test_drain_durations_sane(self):
        from paddle_tpu.profiler import native_tracer as nt
        nt.set_armed(True)
        nid = nt.intern("d")
        t0 = nt.now_ns()
        time.sleep(0.01)
        nt.record(nid, t0, nt.now_ns())
        spans = nt.drain()
        nt.set_armed(False)
        mine = [s for s in spans if s[0] == "d"]
        assert mine
        dur_ms = (mine[-1][2] - mine[-1][1]) * 1000
        assert 5 < dur_ms < 100

    def test_interleaved_spans_pair_correctly(self):
        # regression: a thread-local stack would swap a/b on interleave
        import paddle_tpu.profiler as prof
        from paddle_tpu.profiler import _HOST_TRACER
        _HOST_TRACER.set_armed(True)
        a = prof.RecordEvent("span-a").begin()
        time.sleep(0.004)
        b = prof.RecordEvent("span-b").begin()
        time.sleep(0.002)
        a.end()
        time.sleep(0.006)
        b.end()
        evs = {e.name: e for e in _HOST_TRACER.drain()}
        _HOST_TRACER.set_armed(False)
        ea, eb = evs["span-a"], evs["span-b"]
        # correct pairing: a holds sleeps 1 and 2 (6 ms or more), b holds
        # 2 and 3 (8 or more), and they overlap as they were begun and
        # ended. A LIFO stack would end b's begin with a's end: "a" 2 ms,
        # starting after "b". A sleep only overshoots, so no upper bound
        # and no comparison of two durations: those read the machine's load
        assert (ea.end - ea.start) * 1000 > 4
        assert (eb.end - eb.start) * 1000 > 6
        assert ea.start < eb.start < ea.end < eb.end
