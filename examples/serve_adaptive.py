"""Serving example: continuous-batching LM decode with online specialization.

    PYTHONPATH=src python examples/serve_adaptive.py
    PYTHONPATH=src python examples/serve_adaptive.py --reduced --arch rwkv6-1.6b
    PYTHONPATH=src python examples/serve_adaptive.py --reduced \
        --prefill-chunk 32 --kv-page-size 8 --scheduler sjf

With no arguments it serves the reduced float32 preset (a CPU-sized
run); pass flags without ``--reduced`` to serve the published config.

Open-loop requests (pseudo-Poisson arrivals, mixed prompt/decode lengths)
flow through the :mod:`repro.serve` engine: admission queue -> scheduler
-> continuous batcher -> phase-disaggregated execution over the paged
per-request KV runtime.  Chunked prefill interleaves with decode steps,
and each phase dispatches through its own ``(phase, bucket)``
specialization contexts — the Controller tunes the serve step's spec
points (the RMSNorm implementation) separately for prefill and decode,
while the bucket boundaries are tuned online against measured goodput by
their own plan handler.  ``--kv-page-size`` sets the KV page size.
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    if len(sys.argv) == 1:
        sys.argv += ["--reduced", "--steps", "240"]
    main()
