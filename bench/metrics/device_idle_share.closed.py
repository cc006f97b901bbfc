"""Device: the reading of ``device_idle_share.open.py``, for the cells
whose loop is closed (it moves their ``output_tok_s``)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_device_idle_share_open_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "device_idle_share.open.py"))
_open = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_open)
read = _open.read
