"""Share of the traced window in which no operation ran on the device
(``_common.idle_pct``)."""

import _common

read = _common.idle_pct
