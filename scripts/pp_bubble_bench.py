"""GPipe bubble-fraction measurement (VERDICT r4 weak #4 / next #6).

The GPipe schedule runs M microbatches through S stages in M + S − 1
ticks; the (S−1) fill/drain ticks compute on garbage, so the schedule
does (M+S−1)/M of the sequential compute — the "bubble".  On the
virtual 8-device CPU mesh every virtual device timeshares the same
physical cores, so TOTAL COMPUTE is what wall-clock measures — the
measured pp/sequential ratio should land on the bubble model itself:

    t_pp / t_seq ≈ (M + S − 1) / M        (+ ppermute/psum overhead)

This script measures `parallel.pipeline.pipeline_apply_sharded` against
the equivalent sequential stage stack for pp ∈ {2, 4, 8} × several M,
prints measured vs model.  On real hardware the same ratio is the
per-device IDLE fraction instead (devices are physical), so the model
column is the prediction for a pod; the structural tick count
(M + S − 1) is asserted exactly in
`test_pipeline.py::test_pipeline_tick_count_is_gpipe_schedule`.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python scripts/pp_bubble_bench.py
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=8").strip()


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp

    from distkeras_tpu.obs.profile import fence
    from distkeras_tpu.parallel.mesh import make_mesh
    from distkeras_tpu.parallel.pipeline import (pipeline_apply_sharded,
                                                 stack_stage_params)

    rng = np.random.default_rng(0)
    D = 768    # big enough that per-tick matmuls dwarf the virtual-mesh
    MB = 64    # collective overhead (at tiny shapes that overhead is the
               # whole measurement); microbatch size fixed, B = MB·M

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    def stage_params(s):
        return {"w": jnp.asarray(rng.normal(size=(D, D)) / np.sqrt(D),
                                 jnp.float32)}

    def timeit(fn, x, reps=3, inner=3):
        jfn = jax.jit(fn)
        fence(jfn(x))
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(inner):
                out = jfn(x)
            fence(out)
            best = min(best, (time.perf_counter() - t0) / inner)
        return best

    print("| S (pp) | M | measured t_pp/t_seq | bubble model (M+S-1)/M |")
    print("|---|---|---|---|")
    for S in (2, 4, 8):
        params = [stage_params(s) for s in range(S)]
        stacked = stack_stage_params(params)
        mesh = make_mesh(S, ("pp",))

        def seq(x, params=params):
            for p in params:
                x = stage_fn(p, x)
            return x

        for M in (S, 2 * S, 4 * S):
            x = jnp.asarray(rng.normal(size=(MB * M, D)), jnp.float32)
            t_seq = timeit(seq, x)

            def pp(x, stacked=stacked, mesh=mesh, M=M):
                return pipeline_apply_sharded(mesh, stage_fn, stacked, x,
                                              num_microbatches=M)
            t_pp = timeit(pp, x)
            model = (M + S - 1) / M
            print(f"| {S} | {M} | {t_pp / t_seq:.2f} | {model:.2f} |",
                  flush=True)


if __name__ == "__main__":
    main()
