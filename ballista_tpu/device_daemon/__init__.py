"""Warm device-runtime daemon: one persistent process owns the TPU.

Every process that inits the TPU platform pays the full claim + backend
init + XLA compile cost, and a chip belongs to one process at a time.
This package moves device ownership into ONE
long-lived daemon process (`python -m ballista_tpu.device_daemon`): it
inits the platform once behind a supervised, phase-instrumented state
machine, owns the device table cache / HBM budget / persistent XLA
compile cache, and serves stage execution to any local client over a
unix-domain socket (Arrow IPC framing; a Flight do_exchange variant
exists where the Flight stack is importable).

Executors and dev exercises attach instead of initing:
`client.attach(config)` under the `ballista.tpu.daemon.*` knobs, with
in-process execution as the always-available fallback (the reason lands
in RUN_STATS daemon_mode/daemon_mode_reason). See docs/device_daemon.md.

Import discipline: this package's `client` module must stay importable
without jax (it is reached from executor/scheduler-adjacent code that
the jax-guard analysis pass keeps off the jax import graph); only
`server` touches the device runtime, and only inside functions.
"""
