"""Shared bootstrap for the runnable demos: make the repo importable.
Pin a demo to the CPU with ``JAX_PLATFORMS=cpu`` on the command line."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
