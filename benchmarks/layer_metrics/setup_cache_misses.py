"""`jax::compile` events of the set-up with `cache: miss`: 0 on a warm cache; over 0 in the second run of a
pair is a fault in the cache's key."""

from benchmarks.lib import run_record

layer = "model"
unit = "count"
source = "program_counter"
moves = "setup_s"


def read(run):
    return run_record.setup_cache_misses(run)
