"""Vision Transformer family (beyond the v2.1 reference's model zoo).

The reference's vision zoo (python/paddle/vision/models/) is conv-only
(LeNet/VGG/ResNet/MobileNet).  On TPU a ViT is the natural flagship
vision model: the whole network is LayerNorm + dense matmuls — exactly
the MXU's shape — where ResNet's small-channel convs use it poorly.  Built entirely from the existing
transformer stack (`nn.TransformerEncoder`, pre-LN) so the encoder is
the SAME code path the text models exercise.
"""
from ... import nn
from ...nn import initializer as I


class PatchEmbed(nn.Layer):
    """Image -> sequence of patch embeddings.

    A stride=patch Conv2D is the canonical formulation; XLA lowers a
    kernel==stride conv to a reshape + one [N_patches, P*P*C] x [P*P*C, D]
    matmul, so patch embedding rides the MXU too.
    """

    def __init__(self, image_size=224, patch_size=16, in_channels=3,
                 embed_dim=768):
        super().__init__()
        if image_size % patch_size:
            raise ValueError(
                f"image_size {image_size} not divisible by patch_size "
                f"{patch_size}")
        self.num_patches = (image_size // patch_size) ** 2
        self.proj = nn.Conv2D(in_channels, embed_dim, patch_size,
                              stride=patch_size)

    def forward(self, x):
        from ... import tensor_api as P

        x = self.proj(x)                       # [B, D, H/P, W/P]
        x = P.flatten(x, 2)                    # [B, D, N]
        return P.transpose(x, [0, 2, 1])       # [B, N, D]


class VisionTransformer(nn.Layer):
    """ViT-B/16-style classifier (Dosovitskiy et al., 2021).

    Pre-LN encoder (`normalize_before=True`), GELU MLP, learned position
    embeddings, prepended class token read out through a LayerNorm +
    Linear head.  Dropout follows the paper's placement: on the embedded
    sequence, inside attention, and inside the MLP (the encoder layer
    owns the latter two).
    """

    def __init__(self, image_size=224, patch_size=16, in_channels=3,
                 embed_dim=768, depth=12, num_heads=12, mlp_ratio=4.0,
                 dropout=0.0, attn_dropout=0.0, num_classes=1000):
        super().__init__()
        self.num_classes = num_classes
        self.patch_embed = PatchEmbed(image_size, patch_size, in_channels,
                                      embed_dim)
        n = self.patch_embed.num_patches
        self.cls_token = self.create_parameter(
            (1, 1, embed_dim), default_initializer=I.TruncatedNormal(std=0.02))
        self.pos_embed = self.create_parameter(
            (1, n + 1, embed_dim),
            default_initializer=I.TruncatedNormal(std=0.02))
        self.pos_dropout = nn.Dropout(dropout)
        layer = nn.TransformerEncoderLayer(
            embed_dim, num_heads, int(embed_dim * mlp_ratio),
            dropout=dropout, activation="gelu", attn_dropout=attn_dropout,
            normalize_before=True)
        self.encoder = nn.TransformerEncoder(layer, depth,
                                             norm=nn.LayerNorm(embed_dim))
        if num_classes > 0:
            self.head = nn.Linear(embed_dim, num_classes)

    def forward(self, x):
        from ... import tensor_api as P

        x = self.patch_embed(x)                            # [B, N, D]
        b = x.shape[0]
        cls = P.expand(self.cls_token, [b, 1, x.shape[2]])
        x = P.concat([cls, x], axis=1) + self.pos_embed
        x = self.pos_dropout(x)
        x = self.encoder(x)                                # [B, N+1, D]
        cls_out = x[:, 0]
        return self.head(cls_out) if self.num_classes > 0 else cls_out


def _vit(patch, dim, depth, heads, **kwargs):
    kwargs.setdefault("patch_size", patch)
    return VisionTransformer(embed_dim=dim, depth=depth, num_heads=heads,
                             **kwargs)


def vit_b_16(pretrained=False, **kwargs):
    return _vit(16, 768, 12, 12, **kwargs)


def vit_b_32(pretrained=False, **kwargs):
    return _vit(32, 768, 12, 12, **kwargs)


def vit_l_16(pretrained=False, **kwargs):
    return _vit(16, 1024, 24, 16, **kwargs)


def vit_s_16(pretrained=False, **kwargs):
    """ViT-Small — the common efficient-training variant."""
    return _vit(16, 384, 12, 6, **kwargs)
