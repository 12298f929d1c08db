"""Model zoo: 11 assigned architectures behind one functional API."""
from .model import Model, Runtime, get_model

__all__ = ["Model", "Runtime", "get_model"]
