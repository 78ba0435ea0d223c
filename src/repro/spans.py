"""The program's host spans: one phase of the query path each.

``span`` is ``jax.profiler.TraceAnnotation``: with no trace running it
costs about a microsecond; under ``jax.profiler.trace`` it is recorded
on the same clock as the device's operations. Spans open once per phase
of a query or round, never per tile.
"""
import jax

span = jax.profiler.TraceAnnotation

PREFIX = "aqp."
QUERY = PREFIX + "query"                      # AQPEngine.query / .heatmap
CLASSIFY = PREFIX + "classify"                # pending-set construction
REFINE = PREFIX + "refine"                    # RefinementDriver.run
GATHER = PREFIX + "gather"                    # a round's gathered segments
READ = PREFIX + "read"                        # RawDataset.read_values
KERNEL = PREFIX + "kernel"                    # one data-plane op call
KERNEL_PACK = KERNEL + ".pack"                # host padding and tables
KERNEL_DISPATCH = KERNEL + ".dispatch"        # jit dispatch, H2D copies
KERNEL_WAIT = KERNEL + ".wait"                # device wait and D2H copy
APPLY = PREFIX + "apply"                      # TileIndex.apply_batch
PREDICT = PREFIX + "predict"                  # ViewportPredictor.observe
# TileIndex init pass phases. On the host ("np") route: cell ids, their
# stable argsort, the permuted axes. On the device route
# (ops.init_order): shipping the axes and dispatching the binning, then
# dispatching the sorts, then waiting for the sorted rows, copying them
# into the permutation and gathering the axes by them.
INIT_BIN = PREFIX + "init.bin"
INIT_SORT = PREFIX + "init.sort"
INIT_PERMUTE = PREFIX + "init.permute"
INIT_META = PREFIX + "init.meta"              # the init metadata's read
# ServingEngine: a tick, each lock-step round of a batched tick, a
# round's per-query fold and staging, and the tick's epoch publication.
# A tick's gathers and its sessions' predictor updates open GATHER and
# PREDICT.
SERVE_TICK = PREFIX + "serve.tick"
SERVE_ROUND = PREFIX + "serve.round"
SERVE_FOLD = PREFIX + "serve.fold"
SERVE_PUBLISH = PREFIX + "serve.publish"

NAMES = (QUERY, CLASSIFY, REFINE, GATHER, READ, KERNEL, KERNEL_PACK,
         KERNEL_DISPATCH, KERNEL_WAIT, APPLY, PREDICT, INIT_BIN, INIT_SORT,
         INIT_PERMUTE, INIT_META)
SERVE_NAMES = (SERVE_TICK, SERVE_ROUND, SERVE_FOLD, SERVE_PUBLISH)
