from repro.distributed.sharding import (  # noqa: F401
    ShardingRules,
    activation_spec,
    constrain,
    default_rules,
    param_pspecs,
    use_rules,
)
