from .specs import (axis_size, logical_to_spec, param_specs, shd, use_rules,
                    current_rules, batch_spec)
