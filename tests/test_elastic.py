"""Elastic membership, failure detection, scale events
(SURVEY §5 failure-detection row; §2.3 elastic row)."""
import os
import subprocess
import sys
import textwrap
import time

from paddle_tpu.distributed.elastic import ElasticManager, Event, \
    start_heartbeat


class TestMembership:
    def test_join_and_clean_leave(self, tmp_path):
        d = str(tmp_path)
        mgr = ElasticManager(d, np_expected=2, dead_timeout=2.0)
        stop0 = start_heartbeat(d, rank=0, interval=0.1)
        stop1 = start_heartbeat(d, rank=1, interval=0.1)
        time.sleep(0.6)
        events = mgr.scan()
        kinds = sorted(e.kind for e in events)
        assert kinds == ["join", "join", "scale_up"]
        assert mgr.membership() == [0, 1]
        assert mgr.is_healthy()

        stop1()   # removes the heartbeat file: a clean LEAVE
        events = mgr.scan()
        kinds = [e.kind for e in events]
        assert "leave" in kinds and "scale_down" in kinds
        assert mgr.membership() == [0]
        assert not mgr.is_healthy()
        stop0()

    def test_dead_worker_detected_by_timeout(self, tmp_path):
        d = str(tmp_path)
        mgr = ElasticManager(d, dead_timeout=0.4)
        stop = start_heartbeat(d, rank=3, interval=0.1)
        time.sleep(0.5)
        assert [e.kind for e in mgr.scan()] == ["join"]
        # silence WITHOUT removing the file — crash semantics
        stop_evt_path = os.path.join(d, "worker_3.hb")
        stop()
        with open(stop_evt_path, "w") as f:
            f.write(str(time.time() - 100))  # stale stamp
        events = mgr.scan()
        assert [e.kind for e in events] == ["dead"]
        assert events[0].rank == 3
        assert mgr.membership() == []

    def test_callbacks_fire(self, tmp_path):
        d = str(tmp_path)
        mgr = ElasticManager(d, dead_timeout=5.0)
        seen = []
        mgr.on(Event.JOIN, lambda ev: seen.append(("join", ev.rank)))
        stop = start_heartbeat(d, rank=7, interval=0.1)
        time.sleep(0.5)
        mgr.scan()
        assert seen == [("join", 7)]
        stop()

    def test_endpoint_regeneration(self, tmp_path):
        d = str(tmp_path)
        mgr = ElasticManager(d, base_endpoint="10.0.0.1:6000")
        s0 = start_heartbeat(d, rank=0, interval=0.1)
        s2 = start_heartbeat(d, rank=2, interval=0.1)
        time.sleep(0.5)
        mgr.scan()
        # densely re-ranked endpoints for the surviving membership
        assert mgr.endpoints() == "10.0.0.1:6000,10.0.0.1:6001"
        s0()
        s2()


def test_launcher_emits_membership_events(tmp_path):
    script = tmp_path / "hb_stub.py"
    script.write_text(textwrap.dedent("""
        import time
        from paddle_tpu.distributed.elastic import start_heartbeat
        stop = start_heartbeat(interval=0.1)   # env-driven (launcher sets it)
        time.sleep(2.0)
        stop()
    """))
    env = dict(os.environ)
    env.pop("PADDLE_TRAINER_ID", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--elastic_dir",
         str(tmp_path / "hb"), str(script)],
        capture_output=True, text=True, env=env, timeout=120,
        cwd="/root/repo")
    assert out.returncode == 0, out.stderr
    assert "Event(join, rank=0" in out.stderr
    assert "Event(join, rank=1" in out.stderr
    assert "Event(scale_up" in out.stderr
