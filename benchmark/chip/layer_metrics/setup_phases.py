"""Where set-up goes: model code and the compile layer.

``setup_init_s``          host clock around building the model, ``initialize``
                          and making the seeded batch on the device.
``setup_compile_s``       XLA backend-compile seconds during set-up, less
                          the time spent reading the persistent cache.
``setup_cache_hit_pct``   compile requests the persistent cache answered.
"""


def read(run):
    log = run.compile_setup
    out = {"setup_init_s": run.setup.get("init"),
           "setup_compile_s": log["compile_s"]}
    if log["cache_requests"]:
        out["setup_cache_hit_pct"] = (100.0 * log["cache_hits"]
                                      / log["cache_requests"])
    return out
