"""Shared example bootstrap: puts the repo root on the import path.

Each example does `import _bootstrap  # noqa: F401` as its first import.
The examples run on the platform JAX resolves from the environment; set
`JAX_PLATFORMS=cpu` to run one without a chip.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
