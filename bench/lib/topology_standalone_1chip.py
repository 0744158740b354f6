"""Topology `standalone_1chip`: scheduler, one executor and the Flight server
in this process (`SessionContext.standalone`), the TPU engine in-process, so
the process that runs the queries is the one that holds the chip: it reports
jax's devices, traces itself and reads its own device's memory. Everything the
benchmark takes from the program is in this file: the system under test and
its counters. The contract a topology keeps is in bench/README.md.
"""

from __future__ import annotations

import os


def devices(config: dict) -> dict:
    """The devices this process sees; the first thing a run asks, and what
    initialises jax's backend."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def open_session(config: dict, data_dir: str):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.ops import native
    from ballista_tpu.plan.provider import ParquetTable

    # the shuffle's row router builds itself from native/ on first use (~7 s
    # of g++ in a new checkout): here, in set-up, not inside the first query
    native.get_lib()
    session = SessionContext.standalone(BallistaConfig(dict(config["session"])),
                                        num_executors=config["num_executors"])
    for table in config["tables"]:
        session.register_table(table, ParquetTable(os.path.join(data_dir, table)))
    return session


def close_session(session) -> None:
    if session is not None:
        session.shutdown()


def start_trace(session, trace_dir: str):
    """This process's profiler session into `trace_dir`; hands back what a
    query and its parts are wrapped in, which writes the trace's own marks."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return jax.profiler.TraceAnnotation


def stop_trace(session) -> None:
    import jax

    jax.profiler.stop_trace()


class Probes:
    """The program's own counters, read between queries."""

    def __init__(self):
        import ballista_tpu.ops.tpu.stage_compiler as sc
        from ballista_tpu.ops.tpu import runtime

        runtime.ensure_jax()
        self._sc, self._runtime = sc, runtime

    def clear_run_stats(self) -> None:
        self._sc.RUN_STATS.clear()

    def run_stats_stages(self) -> dict:
        """RunStats per stage since the last clear: numbers and short strings."""
        return {tag: {k: v for k, v in rec.items()
                      if isinstance(v, (int, float, str, list, tuple))}
                for tag, rec in self._sc.RUN_STATS.stages().items()}

    def outcomes(self) -> dict:
        snap = self._sc.STAGE_OUTCOMES.snapshot()
        return {k: snap[k] for k in self._sc.StageOutcomes.KINDS}

    def outcomes_recent(self) -> list:
        return [list(r) for r in self._sc.STAGE_OUTCOMES.snapshot()["recent"]
                if r[1] != "device"]

    def compile_cache(self) -> dict:
        return self._runtime.compile_cache_stats()

    def memory_stats(self) -> tuple[dict, dict]:
        """The numbers of the fullest device's `memory_stats()` (here: the one
        device the executor uses) and every device's peak bytes by its name."""
        import jax

        device = jax.devices()[0]
        stats = {k: v for k, v in (device.memory_stats() or {}).items()
                 if isinstance(v, (int, float))}
        return stats, {f"{device.platform}:{device.id}": stats.get("peak_bytes_in_use", 0)}
