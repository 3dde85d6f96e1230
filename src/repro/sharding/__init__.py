from repro.sharding.specs import param_specs  # noqa: F401
