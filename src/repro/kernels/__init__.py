"""Pallas TPU kernels for logzip hot spots (+ pure-jnp oracles in ref.py).

- simcount:        phi(a,b)=|a cap b| similarity, clustering inner loop
- wildcard_match:  batched greedy-'*' template matching (the trie, TPU-native)

Wrappers with host/pod conveniences live in ops.py. The backend picks
the mode: on a CPU (the test suite) the kernels run in Pallas interpret
mode, on a TPU they run compiled (``python chip_smoke.py`` checks that
path end to end).
"""

from . import ops, ref
from .simcount import simcount
from .wildcard_match import wildcard_match

__all__ = ["ops", "ref", "simcount", "wildcard_match"]
