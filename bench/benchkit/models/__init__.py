"""Model families, one module each, named by a configuration file's
published ``model_type`` (see :func:`benchkit.engine.adapter`).  Each
holds ``model_config(cfg)``, ``program_params(weights, mcfg)`` and
``work(cfg)``."""
