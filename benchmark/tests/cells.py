"""Run a cell of BENCHMARK.json at a test size on the CPU, skipping only the
harness's look for a chip."""

import time

import jax

from benchmark import harness

SIZES = {
    "serve": {"entities": 128, "sessions": 8},
    "synctest": {"entities": 256, "backend": "pallas-interpret"},
}


def run(workload, *, seed=2**31 + 7, seconds=1.0, control=False, sizes=None,
        spec=None, root=harness.ROOT):
    c = harness.resolve(spec or harness.load_spec(), workload, root=root)
    sizes = sizes or SIZES[c.traffic["driver"]]
    devices = jax.devices()[: c.cell["chips"]]
    return harness.run_cell(c, seed=seed, seconds=seconds, trace=False,
                            t_process=time.perf_counter(), control=control,
                            devices=devices, sizes=sizes)
