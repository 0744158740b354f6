"""Topology `holders`: a stand-in for a cluster whose chips live in other
processes, with no program in it. `chips` holder processes (holder.py), each
pinned to one chip by the environment `runtime.bind_process_ordinal` writes
(harmless on the CPU backend), each tracing itself; this process, which runs
the "queries", never imports jax. A query is a burst of a jitted loop on the
holders the configuration's `bursts` names for it, all at once; its answer is
each burst's start and end on the holder's wall clock. bench/tests uses it to
drive cell.py end to end, and clock_experiment.py on a machine with chips."""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
QUERIES = os.path.join(os.path.dirname(os.path.dirname(HERE)), "queries")
TPU_PORT_BASE = 8476
_holders: list = []  # started by devices(), ended by close_session()


class Holder:
    def __init__(self, ordinal: int, burst: dict):
        port = TPU_PORT_BASE + ordinal
        env = {**os.environ, "TPU_VISIBLE_CHIPS": str(ordinal),
               "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS": "1,1,1",
               "TPU_PROCESS_ADDRESSES": f"localhost:{port}", "TPU_PROCESS_PORT": str(port),
               "CLOUD_TPU_TASK_ID": "0"}
        self.ordinal = ordinal
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "holder.py"), str(burst["size"]),
             str(burst["iters"])], env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True)

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"holder {self.ordinal} ended (exit {self.proc.wait()})")
        return json.loads(line)


def ask_all(holders, **cmd) -> list[dict]:
    for h in holders:
        h.send(**cmd)
    return [h.read() for h in holders]


def devices(config: dict) -> dict:
    _holders.extend(Holder(i, config["burst"]) for i in range(config["chips"]))
    try:
        seen = [h.read() for h in _holders]  # each one's first line: the devices it sees
    except RuntimeError:
        close_session(None)  # a holder that could not start: leave none behind
        raise
    return {"platform": seen[0]["platform"], "kind": seen[0]["kind"],
            "count": sum(d["count"] for d in seen)}


class Session:
    def __init__(self, bursts: dict):
        self.by_text = {}
        for query, ordinals in bursts.items():
            with open(os.path.join(QUERIES, f"{query}.sql")) as f:
                self.by_text[f.read()] = [_holders[i] for i in ordinals]

    def sql(self, text: str):
        holders = self.by_text[text]

        class Frame:
            def collect(self):
                import pyarrow as pa

                done = ask_all(holders, cmd="burst")
                return pa.table({"holder": [h.ordinal for h in holders],
                                 "start_ns": [d["start_ns"] for d in done],
                                 "end_ns": [d["end_ns"] for d in done]})

        return Frame()


def open_session(config: dict, data_dir: str) -> Session:
    return Session(config["bursts"])


def close_session(session) -> None:
    for h in _holders:
        h.proc.stdin.close()
    for h in _holders:
        h.proc.wait(timeout=60)
    _holders.clear()


def start_trace(session, trace_dir: str):
    dirs = [os.path.join(trace_dir, f"holder{h.ordinal}") for h in _holders]
    for h, d in zip(_holders, dirs):  # a directory each: processes of one host name their files alike
        os.makedirs(d)
        h.send(cmd="start_trace", dir=d)
    for h, d in zip(_holders, dirs):  # beside its file, the mark of the burst the holder began with
        with open(os.path.join(d, "sync.json"), "w") as f:
            json.dump(h.read(), f)
    return lambda name: contextlib.nullcontext()  # no profiler here: the record's marks count


def stop_trace(session) -> None:
    ask_all(_holders, cmd="stop_trace")


class Probes:
    """No program, so no counters: only the holders' memory."""

    def clear_run_stats(self) -> None:
        pass

    def run_stats_stages(self) -> dict:
        return {}

    def outcomes(self) -> dict:
        return {"device": 0}

    def outcomes_recent(self) -> list:
        return []

    def compile_cache(self) -> dict:
        return {"requests": 0, "hits": 0, "misses": 0, "dir": ""}

    def memory_stats(self) -> tuple[dict, dict]:
        stats = ask_all(_holders, cmd="memory")
        peaks = {f"holder{h.ordinal}": s.get("peak_bytes_in_use", 0)
                 for h, s in zip(_holders, stats)}
        return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0)), peaks
