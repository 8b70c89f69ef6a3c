"""The benchmark's own tests run on the CPU at tiny sizes (Pallas in
interpret mode); nothing here needs or touches a chip."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
