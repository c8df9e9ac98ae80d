"""MicroNN-TPU: disk/HBM-tiered updatable vector search (MicroNN, Apple
2025), an embeddable vector database whose scan tier lives in
accelerator memory and whose durable store is SQLite.

Public surface:
    repro.storage.MicroNN        -- the embeddable engine (paper Fig. 1)
    repro.core                   -- C1-C6 algorithm modules
    repro.serving.FrontDoor      -- concurrent, coalescing query front door
    repro.distributed            -- pod-scale distributed ANN search
    repro.fleet.Fleet            -- multi-tenant engines, one FramePool
"""
__version__ = "1.0.0"
