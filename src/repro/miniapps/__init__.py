"""Streaming Mini-Apps (paper §5): MASS sources + MASA processors."""
from repro.miniapps.mass import (
    SOURCES,
    KMeansClusterSource,
    KMeansStaticSource,
    LightsourceTemplateSource,
    RateStep,
    RateStepScenario,
    ServingTraceSource,
    SourceConfig,
    StreamSource,
    TokenSource,
)
from repro.miniapps.detector import DetectorSimSource
from repro.miniapps.masa import (
    PROCESSORS,
    LMServeApp,
    LMTrainApp,
    ReconstructionApp,
    StreamingKMeans,
)

__all__ = [
    "DetectorSimSource",
    "KMeansClusterSource",
    "KMeansStaticSource",
    "LMServeApp",
    "LMTrainApp",
    "LightsourceTemplateSource",
    "PROCESSORS",
    "RateStep",
    "RateStepScenario",
    "ReconstructionApp",
    "SOURCES",
    "ServingTraceSource",
    "SourceConfig",
    "StreamSource",
    "StreamingKMeans",
    "TokenSource",
]
