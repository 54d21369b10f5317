"""The benchmark's own tests run on the CPU, in one process each, with
the program's persistent compile cache off."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["LOG_PARSER_TPU_XLA_CACHE"] = "0"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
