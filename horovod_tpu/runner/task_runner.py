"""Worker-side stub for the interactive `run(fn)` API.

Reference: the gloo_run exec path that wraps the user function for
horovod.run (runner/task_fn-style execution). Loads the pickled function,
initializes the framework, runs it, writes the pickled result where the
launcher expects it.
"""

from __future__ import annotations

import os
import pickle


def main() -> None:
    fn_path = os.environ["HOROVOD_RUN_FUNC_FILE"]
    out_dir = os.environ["HOROVOD_RUN_RESULT_DIR"]
    rank = int(os.environ.get("HOROVOD_RANK", "0"))
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # One CPU device per worker process (process == rank): a parent
        # pytest session may leak its virtual-device flag and rank
        # emulation — scrub both BEFORE the first backend touch.
        os.environ["XLA_FLAGS"] = " ".join(
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        os.environ.pop("HOROVOD_TPU_EMULATE_RANKS", None)
    with open(fn_path, "rb") as f:
        fn = pickle.load(f)
    result = fn()
    tmp = os.path.join(out_dir, f".rank_{rank}.tmp")
    with open(tmp, "wb") as f:
        pickle.dump(result, f)
    os.replace(tmp, os.path.join(out_dir, f"rank_{rank}.pkl"))


if __name__ == "__main__":
    main()
