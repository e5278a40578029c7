"""``python -m repro`` entry point (see repro/api/cli.py)."""
from repro.api.cli import main
from repro.compile_cache import enable_compile_cache

enable_compile_cache()
raise SystemExit(main())
