"""``python -m tony_tpu.executor`` — the container entry point (reference:
``TaskExecutor.main``, launched by the NM per ``buildContainerLaunchContext``)."""

import sys

from tony_tpu.executor import main

if __name__ == "__main__":
    sys.exit(main())
