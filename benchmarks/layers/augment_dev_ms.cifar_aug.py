"""Device milliseconds per fit in RandomPatchCifarAugmented's
augmentation: the programs that cut the training images' random crops
(``jit_random_patches``: two products with 0/1 selectors a crop), mirror
half of them (``jit_random_transform``), cut the test images' ten crops
(``jit_center_corner_patches``) and lay every crop out as a row
(``jit_vectorize_images``). Nothing is read, and None returned, where
the trace holds none of them: another app, a parent commit."""
from benchmarks.layers import _common

AUGMENT_PROGRAMS = ("jit_random_patches", "jit_random_transform",
                    "jit_center_corner_patches", "jit_vectorize_images")


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(run, AUGMENT_PROGRAMS)
    return None if not fits or seconds is None else 1e3 * seconds / fits
