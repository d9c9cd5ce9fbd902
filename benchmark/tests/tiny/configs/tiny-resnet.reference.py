"""Test only: the resnet50 reference, at the tiny configuration (resnet)."""
from benchmark.spec import CODE_DIR, load_module

globals().update({k: v for k, v in vars(load_module(
    CODE_DIR / "configs" / "resnet50.reference.py", "resnet_ref")).items()
    if not k.startswith("__")})
