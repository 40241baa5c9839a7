"""The three service processes of a deployment, booted as a user boots them:
``python -m detectmateservice_tpu.cli --settings …`` each, over ``ipc://``
(the detector through ``lib/stage_main.py``: the same ``cli.main``, with the
memory account of its executables written down beside it).

Plumbing copied from ``chip_smoke.py`` (``Stage``, ``build_stages``); what a
stage runs with comes from the configuration's file, and the harness adds
only the wiring (addresses, ports, file locations, the seed).

Only the detector child touches jax. This module never imports it.
"""
from __future__ import annotations

import copy
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Dict

import yaml

ORDER = ("output", "detector", "parser")   # boot order: downstream first


class HarnessFailure(Exception):
    """A stage died, a wait timed out, or the device is not the one asked
    for: the run ends with a non-zero code and prints no result."""


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def http_json(port: int, path: str, post: bool = False, timeout: float = 10.0):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=b"" if post else None,
                                 method="POST" if post else "GET")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class Stage:
    """One service process: settings + config on disk, output to a log."""

    def __init__(self, name: str, work: str, repo: str, settings: dict,
                 component: dict, programs_path: str = ""):
        self.name = name
        self.programs_path = programs_path
        self.port = settings["http_port"]
        self.settings_path = os.path.join(work, f"{name}_settings.yaml")
        self.log_path = os.path.join(work, f"{name}.out")
        self.proc = None
        self._work = work
        self._repo = repo
        with open(settings["config_file"], "w", encoding="utf-8") as fh:
            yaml.safe_dump(component, fh)
        with open(self.settings_path, "w", encoding="utf-8") as fh:
            yaml.safe_dump(settings, fh)

    def start(self) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = self._repo + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        # the stage that holds the chip boots through lib/stage_main.py: the
        # same cli.main, plus XLA's memory account of each executable it
        # compiles ahead of time, which the program does not export
        entry = (["-m", "detectmateservice_tpu.cli"]
                 if not self.programs_path else
                 [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "stage_main.py"),
                  "--programs", self.programs_path])
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [sys.executable, *entry, "--settings", self.settings_path],
                stdout=log, stderr=subprocess.STDOUT, env=env,
                cwd=self._work)

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise HarnessFailure(f"{self.name} exited with code {rc} — log "
                                 f"tail:\n{self.log_tail()}")

    def wait_running(self, timeout_s: float) -> None:
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            self.check_alive()
            try:
                if http_json(self.port, "/admin/status")["status"]["running"]:
                    return
            except (OSError, urllib.error.URLError, KeyError, ValueError):
                pass
            time.sleep(0.25)
        raise HarnessFailure(f"{self.name} not running after "
                             f"{timeout_s:.0f}s — log tail:\n"
                             f"{self.log_tail()}")

    def shutdown(self, timeout_s: float = 60.0) -> int:
        """Ask the service to stop, reap it, return its exit code."""
        if self.proc is None:
            return 0
        if self.proc.poll() is None:
            try:
                http_json(self.port, "/admin/shutdown", post=True)
            except (OSError, urllib.error.URLError, ValueError):
                self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
                return -9
        return self.proc.returncode

    def log_tail(self, n_bytes: int = 3000) -> str:
        try:
            with open(self.log_path, "rb") as fh:
                fh.seek(0, os.SEEK_END)
                fh.seek(max(0, fh.tell() - n_bytes))
                return fh.read().decode("utf-8", "replace")
        except OSError:
            return "(no log)"


def wait_for(predicate, timeout_s: float, what: str, stages=(),
             poll_s: float = 0.1) -> None:
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        for stage in stages:
            stage.check_alive()
        if predicate():
            return
        time.sleep(poll_s)
    raise HarnessFailure(f"timed out after {timeout_s:.0f}s waiting for "
                         f"{what}")


def scorer_of(config: dict) -> dict:
    """The scorer block of a configuration (one detector class, one block)."""
    (block,) = config["stages"]["detector"]["component"]["detectors"].values()
    return block


def build_stages(work: str, repo: str, config: dict, seed: int,
                 sink_addr: str) -> Dict[str, Stage]:
    """Write each stage's files under ``work`` and return the stages, not
    started. The configuration's file states settings and component blocks;
    wiring is added here and nowhere else."""
    addr = {name: f"ipc://{work}/{name}.ipc" for name in ORDER}
    downstream = {"parser": addr["detector"], "detector": addr["output"],
                  "output": sink_addr}
    templates = os.path.join(work, "templates.txt")
    with open(templates, "w", encoding="utf-8") as fh:
        fh.write(config["traffic_source"]["template"] + "\n")
    stages = {}
    for name in ORDER:
        spec = copy.deepcopy(config["stages"][name])
        settings = dict(
            spec["settings"], engine_addr=addr[name],
            out_addr=[downstream[name]], http_port=free_port(),
            config_file=os.path.join(work, f"{name}_config.yaml"),
            log_dir=os.path.join(work, "logs"))
        component = spec["component"]
        if name == "parser":
            for block in component["parsers"].values():
                block.setdefault("params", {})["path_templates"] = templates
        if name == "detector":
            settings.update(
                checkpoint_dir=os.path.join(work, "checkpoint"),
                profile_dir=os.path.join(work, "profile"))
            # weights come from --seed: the scorer initialises from it
            scorer_of({"stages": {"detector": spec}})["seed"] = int(
                seed % (2 ** 31))
        stages[name] = Stage(
            name, work, repo, settings, component,
            programs_path=(os.path.join(work, "detector_programs.jsonl")
                           if name == "detector" else ""))
    return stages
