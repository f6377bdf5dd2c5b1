# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
from jax.experimental.pallas import tpu as _pltpu

# Mosaic compiler params; every kernel module imports them from here.
CompilerParams = _pltpu.CompilerParams
