from .sharding import Parallelism, param_specs
