from repro.models.registry import (batch_extras, build_model, input_specs,
                                   make_batch)

__all__ = ["batch_extras", "build_model", "input_specs", "make_batch"]
