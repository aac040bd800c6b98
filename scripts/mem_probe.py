#!/usr/bin/env python3
"""Memory probe: how much HBM the dense mixed-window program needs on a TPU.

    python scripts/mem_probe.py

The compiler reports a few GB of temp for the session's mixed-window
program (``Partitioner``'s donated ``_run_window_mixed``), while the
runtime's ``peak_bytes_in_use`` after a session stays near the state's
size. This probe separates the readings, on one chip, in one process:

1. ``memory_analysis()`` of the program at n = 2**22, max_deg = 192,
   K = 16, W = 256: argument, temp, alias and output bytes.
2. That same compiled executable, run once on a state built on the
   device, with ``peak_bytes_in_use`` read before and after.
3. The executable run again while a filler buffer holds all of HBM but
   the state and half the reported temp. If the temp is allocated when
   the program runs, this run fails for memory; if it runs, the temp is
   not held at once.
4. The program compiled at n = 10,000,000, which the compiler is
   expected to refuse.

It exits non-zero without a TPU.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

MAX_DEG, K, W = 192, 16, 256


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.api.partitioner import _mixed_donated
    from repro.core.config import EngineConfig
    from repro.core.state import init_state
    from repro.graph.stream import powerlaw_churn

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"mem_probe: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 1
    cfg = EngineConfig(k_max=K, k_init=1, autoscale=True, max_cap=3000)
    limit = dev.memory_stats()["bytes_limit"]
    print(f"device {dev.device_kind} bytes_limit={limit}", flush=True)

    def peak():
        return dev.memory_stats()["peak_bytes_in_use"]

    def compiled(n):
        st = jax.eval_shape(lambda: init_state(n, MAX_DEG, K, 1, 0))
        ev = (jax.ShapeDtypeStruct((W,), jnp.int32),
              jax.ShapeDtypeStruct((W,), jnp.int32),
              jax.ShapeDtypeStruct((W, MAX_DEG), jnp.int32),
              jax.ShapeDtypeStruct((), jnp.int32))
        return _mixed_donated.lower(st, *ev, policy="sdp", cfg=cfg).compile()

    n = 1 << 22
    exe = compiled(n)
    m = exe.memory_analysis()
    temp = m.temp_size_in_bytes
    print(f"n={n}: args={m.argument_size_in_bytes} temp={temp} "
          f"alias={m.alias_size_in_bytes} out={m.output_size_in_bytes}",
          flush=True)

    s = powerlaw_churn(n, W, max_deg=MAX_DEG, seed=0)
    ev = (jnp.asarray(s.etype), jnp.asarray(s.vertex), jnp.asarray(s.nbrs),
          jnp.int32(0))
    state = jax.jit(lambda: init_state(n, MAX_DEG, K, 1, 0))()
    jax.block_until_ready(state)
    p0 = peak()
    state = exe(state, *ev)
    jax.block_until_ready(state)
    print(f"n={n}: ran the compiled window; peak_bytes_in_use before={p0} "
          f"after={peak()}", flush=True)

    state_bytes = sum(a.nbytes for a in jax.tree.leaves(state))
    filler_bytes = limit - state_bytes - temp // 2
    filler = jnp.zeros((filler_bytes // 4,), jnp.int32, device=dev)
    jax.block_until_ready(filler)
    try:
        state = exe(state, *ev)
        jax.block_until_ready(state)
        verdict = "ran: the reported temp is not held at once"
    except (jax.errors.JaxRuntimeError, ValueError) as e:
        # loading a program whose temp does not fit raises ValueError
        # ("Attempting to reserve ... at the bottom of memory")
        verdict = f"failed: {str(e).splitlines()[0][:200]}"
    print(f"n={n}: with a {filler.nbytes} B filler leaving {temp // 2} B "
          f"of the temp free, the window {verdict}; peak_bytes_in_use="
          f"{peak()}", flush=True)
    del filler, state

    n_big = 10_000_000
    try:
        m = compiled(n_big).memory_analysis()
        print(f"n={n_big}: compiled, args={m.argument_size_in_bytes} "
              f"temp={m.temp_size_in_bytes}", flush=True)
    except (jax.errors.JaxRuntimeError, ValueError) as e:
        print(f"n={n_big}: compile refused: {str(e).splitlines()[0][:200]}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
