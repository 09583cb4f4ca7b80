import os
import sys

# the benchmark's tests run on the CPU at small sizes
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)
