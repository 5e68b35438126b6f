"""``half_the_training_rows_left_out`` for ``timit_50x4096``: its frames
are CSV, read through the loader ``mnist_random_fft_32`` reads with, so
the fault is that file's, run here under this configuration's name."""
import os
import runpy

runpy.run_path(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "mnist_random_fft_32.py"), run_name="__main__")
