"""Test only: the gpt2-medium reference, at the tiny configuration."""
from benchmark.spec import CODE_DIR, load_module

globals().update({k: v for k, v in vars(load_module(
    CODE_DIR / "configs" / "gpt2-medium.reference.py", "gpt2_ref")).items()
    if not k.startswith("__")})
