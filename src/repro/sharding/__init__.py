from repro.sharding.ctx import ShardCtx
from repro.sharding.policies import (batch_specs, cache_specs, named,
                                     param_specs, specee_specs, state_specs)
from repro.sharding.serving import decode_state_specs, engine_shardings
