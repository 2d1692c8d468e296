from repro.roofline.analyze import (  # noqa: F401
    analyze_hlo, roofline_terms, HloCost,
)
