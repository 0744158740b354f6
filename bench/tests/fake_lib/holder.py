"""A chip holder of the fake topology `holders` (topology_holders.py): a plain
python process that sees one device, runs a jitted loop in bursts when told
to, and traces itself. One JSON object a line on standard input, one reply a
line on standard output; it ends when its input does."""

import json
import sys
import time


def main() -> int:
    size, iters = int(sys.argv[1]), int(sys.argv[2])
    import jax
    import jax.numpy as jnp

    @jax.jit
    def burst(x):
        return jax.lax.fori_loop(0, iters, lambda _, x: jnp.tanh(x @ x) + 0.5, x)

    x = jnp.full((size, size), 0.001, jnp.float32)
    burst(x).block_until_ready()  # compiled before the first command

    def reply(obj: dict) -> None:
        print(json.dumps(obj), flush=True)

    devs = jax.devices()
    reply({"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)})
    def timed_burst() -> dict:
        start = time.time_ns()
        with jax.profiler.TraceAnnotation("burst"):  # the same burst on the file's host plane
            burst(x).block_until_ready()
        return {"start_ns": start, "end_ns": time.time_ns()}

    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "burst":
            reply(timed_burst())
        elif cmd["cmd"] == "start_trace":
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(cmd["dir"], profiler_options=options)
            # one burst before any query: every file shows its device, also that
            # of a holder no query uses, and holds one mark outside the window
            reply(timed_burst())
        elif cmd["cmd"] == "stop_trace":
            jax.profiler.stop_trace()
            reply({})
        elif cmd["cmd"] == "memory":
            reply({k: v for k, v in (devs[0].memory_stats() or {}).items()
                   if isinstance(v, (int, float))})
    return 0


if __name__ == "__main__":
    sys.exit(main())
