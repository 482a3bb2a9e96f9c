from apex_tpu.utils.platform import (  # noqa: F401
    pallas_interpret,
)
from apex_tpu.utils.math import (  # noqa: F401
    cdiv,
    divide,
    ensure_divisibility,
    round_up_to_multiple,
)
