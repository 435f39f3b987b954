"""Experiment scheduler — subprocess trials with timeout/OOM capture and a
resumable experiment log.

Reference: ``deepspeed/autotuning/scheduler.py:27`` (ResourceManager): the
reference schedules each candidate as a separate training JOB, polls for
completion, parses metric files, and records failures without killing the
sweep. TPU-native analogue: one chip (or virtual mesh) per host, so the
resource pool is this machine — but trial ISOLATION still matters: a
candidate that OOMs HBM, hangs in compilation, or crashes the XLA runtime
must not take the tuner down. Each trial therefore runs in a fresh
subprocess (``trial_runner.py``) with a hard timeout; the parent records
ok/oom/timeout/crash per trial in ``experiments.jsonl`` and SKIPS already-
recorded specs on restart — the reference's experiment-resume behavior.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from typing import Optional

from ..utils.jax_env import require_chip_free
from ..utils.logging import logger

_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Out of memory",
    "out of memory",
    "OOM",
    "Allocation failure",
)


def spec_key(spec: dict) -> str:
    """Stable identity of a trial spec (the resume key)."""
    return hashlib.sha1(json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]


class ExperimentScheduler:
    """Run trial specs in isolated subprocesses; log results durably.

    A spec is a JSON dict understood by ``trial_runner.py``:
      {"model_cfg": {TransformerConfig kwargs}, "ds_config": {...},
       "batch": {"size": B, "seq": S, "vocab": V}, "steps": n, "warmup": n}
    """

    def __init__(self, exp_dir: str, trial_timeout: float = 600.0,
                 env: Optional[dict] = None):
        self.exp_dir = exp_dir
        self.trial_timeout = trial_timeout
        self.env = env
        os.makedirs(exp_dir, exist_ok=True)
        self.log_path = os.path.join(exp_dir, "experiments.jsonl")
        self._done: dict[str, dict] = {}
        if os.path.exists(self.log_path):
            with open(self.log_path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                        self._done[rec["key"]] = rec
                    except (ValueError, KeyError):
                        continue  # torn write from a killed run — re-measure
            if self._done:
                logger.info(
                    f"autotune scheduler: resuming {self.log_path} with "
                    f"{len(self._done)} recorded trials")

    # ------------------------------------------------------------------
    def run_trial(self, spec: dict) -> dict:
        """Execute one spec (or return its recorded result). The returned
        record always has ``status`` in ok|oom|timeout|crash."""
        key = spec_key(spec)
        if key in self._done:
            return self._done[key]
        rec = {"key": key, "spec": spec}
        spec_path = os.path.join(self.exp_dir, f"trial_{key}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        cmd = [sys.executable, "-m", "deepspeed_tpu.autotuning.trial_runner", spec_path]
        env = dict(os.environ)
        if self.env:
            env.update(self.env)
        require_chip_free("ExperimentScheduler", env)
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=self.trial_timeout,
                env=env,
            )
            out_line = None
            for line in reversed((proc.stdout or "").splitlines()):
                if line.startswith("{"):
                    out_line = line
                    break
            if proc.returncode == 0 and out_line:
                rec.update(json.loads(out_line))
                rec.setdefault("status", "ok")
            else:
                tail = (proc.stderr or proc.stdout or "")[-2000:]
                status = "oom" if any(m in tail for m in _OOM_MARKERS) else "crash"
                rec.update({
                    "status": status,
                    "error": f"rc={proc.returncode}: " + tail[-400:],
                })
        except subprocess.TimeoutExpired as e:
            tail = ""
            for stream in (e.stderr, e.stdout):
                if stream:
                    tail += stream.decode() if isinstance(stream, bytes) else stream
            status = "oom" if any(m in tail for m in _OOM_MARKERS) else "timeout"
            rec.update({"status": status,
                        "error": f"timeout after {self.trial_timeout}s"})
        self._record(rec)
        return rec

    def _record(self, rec: dict):
        self._done[rec["key"]] = rec
        with open(self.log_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())

    @property
    def results(self) -> list[dict]:
        return list(self._done.values())
