from repro.parallel.sharding import (EXCHANGE_COUNTERS, count_exchange,
                                     exchange_all_gather, exchange_pmax,
                                     pad_site_axis, site_mesh, site_pad)

__all__ = ["EXCHANGE_COUNTERS", "count_exchange", "exchange_all_gather",
           "exchange_pmax", "pad_site_axis", "site_mesh", "site_pad"]
