"""Rule catalog (DESIGN.md §15).  Each module defines one rule class;
``all_rules()`` instantiates the full set in code order."""
from .rpr001_raw_jit import RawJitInServe
from .rpr002_host_sync import HostSyncInJitted
from .rpr003_static_args import ScalarArgsWithoutStatic
from .rpr004_accum_dtype import KernelAccumDtype
from .rpr005_serve_loop import SingleServeLoop
from .rpr006_clock_seam import ClockSeamBypass
from .rpr007_tile_assert import BareTileAssert
from .rpr008_pool_raise import PoolRaiseInServe
from .rpr009_obs_bypass import ObsBypassInServe
from .rpr010_kernel_name import UnnamedPallasCall

RULE_CLASSES = [RawJitInServe, HostSyncInJitted, ScalarArgsWithoutStatic,
                KernelAccumDtype, SingleServeLoop, ClockSeamBypass,
                BareTileAssert, PoolRaiseInServe, ObsBypassInServe,
                UnnamedPallasCall]


def all_rules():
    return [cls() for cls in RULE_CLASSES]


def rules_by_code(*codes):
    by_code = {cls.code: cls for cls in RULE_CLASSES}
    return [by_code[c]() for c in codes]
