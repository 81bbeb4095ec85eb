from repro.training.steps import (SERVE_SEARCH_POINTS, SHARDING_PROFILES,
                                  cross_entropy, make_decode_builder,
                                  make_prefill_builder, make_serve_builder,
                                  make_train_builder, phase_context_fn,
                                  run_options_from_spec)

__all__ = ["SERVE_SEARCH_POINTS", "SHARDING_PROFILES", "cross_entropy",
           "make_decode_builder", "make_prefill_builder",
           "make_serve_builder", "make_train_builder", "phase_context_fn",
           "run_options_from_spec"]
