"""Share of a decode scheduler's construction that lies under none of its named
parts (the cache's allocation, the weights' placement, warm-up): 100 x the sum
of the cell ``serving.decode.build.unspanned`` over that of
``serving.decode.build``, over the process.  The program keeps the difference
itself: the cells' own (``serving.decode.build`` - ``serving.cache.allocate`` -
``serving.model_load`` - ``serving.decode.warmup``) would take out every OTHER
span named ``serving.model_load`` too (the model store's load, and the
``make_params`` of six of this benchmark's model files, which lies outside the
construction: the difference read -19% warm and -141% cold in
``kanana2_standing_decode``)."""
from chipbench import loop_cells


def read(observed):
    build = loop_cells.sum_s("serving.decode.build")
    between = loop_cells.sum_s("serving.decode.build.unspanned")
    if build is None or between is None:
        return None
    return 100.0 * between / build if build else 0.0
