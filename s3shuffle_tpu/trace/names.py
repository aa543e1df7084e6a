"""Span-name registry — the single source of truth for trace span/counter
names, mirroring ``metrics/names.py`` for the metric plane.

Every ``trace.span("...")`` / ``trace.count("...")`` /
``trace.flight_record("...")`` call site in the package must use a name
declared here (shuffle-lint rule TRC01), and every declared name must be
used somewhere (the reverse-direction drift test in
``tests/test_shuffle_lint.py``). The table is a **pure literal** — the
linter loads it by AST parsing alone and never imports this module.

Kinds:

- ``span``    — a timed ``with trace.span(name): ...`` region (Chrome-trace
  complete event; also a flight-recorder record name);
- ``counter`` — a ``trace.count(name)`` accumulator exported in the trace
  file's ``otherData.counters``.

Naming follows ``<plane>.<operation>``; the plane prefix is what the
critical-path analyzer (``tools/critical_path.py``) buckets blame by, so a
new span name lands in the right blame category by construction.

Every span's self time also lands in the registry counter
``trace_span_self_us_total{span}`` (utils/trace.py). The spans at the layer
boundaries of a map task (``task.map`` → ``record.partition``,
``record.serialize``, ``codec.encode.harvest``, ``write.commit``) and of a
reduce task (``task.reduce`` → ``read.prefetch_wait``,
``record.deserialize``, ``codec.decode.harvest``, ``agg.sort``), and the
per-launch spans on the codec threads (``codec.encode.batch`` →
``stage``/``device``/``assemble``; ``codec.decode.batch`` →
``parse``/``device``/``emit``) are what the benchmark's per-layer metrics
read. No span sits inside a per-record or per-block loop.
"""

#: name -> kind ("span" | "counter"); pure literal, AST-parsed by lint
KNOWN_SPANS = {
    "agg.sort": "span",
    "codec.compile": "span",
    "codec.compress_batch": "span",
    "codec.decode.batch": "span",
    "codec.decode.device": "span",
    "codec.decode.emit": "span",
    "codec.decode.harvest": "span",
    "codec.decode.parse": "span",
    "codec.encode.assemble": "span",
    "codec.encode.batch": "span",
    "codec.encode.device": "span",
    "codec.encode.harvest": "span",
    "codec.encode.stage": "span",
    "driver.collect": "span",
    "driver.compact": "span",
    "driver.job": "span",
    "driver.map_stage": "span",
    "driver.publish_snapshot": "span",
    "driver.reduce_stage": "span",
    "driver.stage_inputs": "span",
    "meta.rpc": "span",
    "read.chunked_prefill": "span",
    "read.index_prefetch": "span",
    "read.prefetch": "span",
    "read.prefetch_wait": "span",
    "read.tasks": "counter",
    "record.deserialize": "span",
    "record.partition": "span",
    "record.serialize": "span",
    "storage.op": "span",
    "task.map": "span",
    "task.reduce": "span",
    "witness.violation": "span",
    "worker.drain": "span",
    "worker.task": "span",
    "write.commit": "span",
    "write.composite_flush": "span",
    "write.upload_chunk": "span",
}
