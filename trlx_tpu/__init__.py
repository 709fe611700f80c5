"""trlx_tpu — a TPU-native (JAX/XLA/pjit/pallas) RLHF framework with the
capability surface of trlx: PPO, ILQL, SFT and RFT fine-tuning of causal
and seq2seq language models, from one chip to multi-host pods via a
single sharding-polymorphic trainer (mesh axes dp/fsdp/tp/sp).
"""

import time

# before the imports below, which are most of the package's own start-up
# cost: the flight stream's `setup` row counts from here (obs/observer.py)
IMPORTED_AT = time.monotonic()

__version__ = "0.1.0"

from trlx_tpu import utils  # noqa: F401
from trlx_tpu.api import train  # noqa: F401
from trlx_tpu.data.configs import TRLConfig  # noqa: F401
from trlx_tpu.utils import logging  # noqa: F401
