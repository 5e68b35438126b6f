"""Host seconds per fit inside the program's ``dag:optimize`` spans in
``cifar_refit``: the optimizer's whole cost over 20 branches, the choice
to hand them to the solver among it.
The reader is ``optimize_host_s.timit``'s (``_ring_spans``: the cell completes three
fits a window, where ``optimize_host_s.refit`` wants ten)."""
from benchmarks.harness import load_module

read = load_module("layers", "optimize_host_s.timit").read
