"""Plain float32 references, one module per architecture, named by the
``REFERENCE`` of each module under ``bench/arch/``."""
