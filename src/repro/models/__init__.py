
from . import model
from .config import ArchConfig, MoESettings, ShapeConfig


__all__ = ["ArchConfig", "MoESettings", "ShapeConfig", "model"]
