"""Regression tests for bench.py's mid-run hang protection — the machinery
that converts a device sync that never returns into a recorded error line
instead of a silent hang.

These run on the CPU backend; nothing here touches a device.
"""
import json
import signal
import subprocess
import sys
import threading
import time

import pytest

import bench


class TestAlarm:
    def test_raises_on_simulated_wedge(self):
        t0 = time.time()
        with pytest.raises(TimeoutError, match="wedge-sim exceeded 1s"):
            with bench._alarm(1, "wedge-sim"):
                time.sleep(30)
        assert time.time() - t0 < 5

    def test_normal_exit_leaves_no_residual_alarm(self):
        with bench._alarm(5, "noop"):
            pass
        assert signal.alarm(0) == 0

    def test_nested_guard_restores_outer_budget(self):
        with bench._alarm(30, "outer"):
            with bench._alarm(2, "inner"):
                pass
            remaining = signal.alarm(0)  # read + disarm the outer
            assert 20 < remaining <= 30
        assert signal.alarm(0) == 0

    def test_off_main_thread_is_noop(self):
        ran = []

        def work():
            with bench._alarm(1, "thread"):
                ran.append(True)

        t = threading.Thread(target=work)
        t.start()
        t.join()
        assert ran == [True]

    def test_hard_exit_fires_when_signal_cannot_deliver(self):
        # a blocked C call never runs bytecode, so the SIGALRM TimeoutError
        # is never raised; the backup thread must print the best-so-far
        # JSON line and hard-exit with code 3
        code = (
            "import threading, bench\n"
            "bench._publish_partial({'metric': 'm', 'value': 1.0,"
            " 'unit': 'u', 'vs_baseline': 2.0})\n"
            "with bench._alarm(-59, 'c-blocked'):\n"  # thread fires at 1s
            "    threading.Event().wait()\n"
        )
        p = subprocess.run([sys.executable, "-c", code], cwd=bench.__file__.rsplit("/", 1)[0],
                           capture_output=True, text=True, timeout=60,
                           env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
        assert p.returncode == 3
        out = json.loads(p.stdout.strip().splitlines()[-1])
        assert out["value"] == 1.0
        assert "hard-wedged" in out["error"]


class TestRecordFailure:
    def test_builds_message_before_dropping_reference(self):
        extras = {}
        try:
            raise RuntimeError("boom-" + "x" * 500)
        except RuntimeError as e:
            bench._record_failure(extras, "k", "stage", e)
        assert extras["k"].startswith("RuntimeError: boom-")
        assert len(extras["k"]) <= 160
