"""How long does the TPU backend take to start, and does an environment
setting change it? Each reading is a fresh process that times its first
`jax.devices()`; settings are interleaved so that drift hits all alike.
`backend_init_s` carries nearly all of the run-to-run spread of `setup_s`
(PERF.md, PR 23); this is the tool that was used to look for a cause.

    python3 benchmarks/tools/probe_backend.py [repeats]
"""

import os
import subprocess
import sys

CHILD = ("import time; t=time.perf_counter(); import jax; "
         "t1=time.perf_counter(); jax.devices(); "
         "print(round(t1-t,3), round(time.perf_counter()-t1,3))")
SETTINGS = {
    "default": {},
    "premapped_512M": {"TPU_PREMAPPED_BUFFER_SIZE": str(512 * 2 ** 20)},
    "premapped_64M": {"TPU_PREMAPPED_BUFFER_SIZE": str(64 * 2 ** 20)},
}


def main() -> None:
    repeats = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    rows = {k: [] for k in SETTINGS}
    for _ in range(repeats):
        for name, extra in SETTINGS.items():
            p = subprocess.run([sys.executable, "-c", CHILD],
                               env=dict(os.environ, **extra),
                               capture_output=True, text=True)
            rows[name].append(p.stdout.strip() or p.stderr[-300:])
    for name, vals in rows.items():
        print(name, "(import_s backend_init_s):", vals)


if __name__ == "__main__":
    main()
